"""The reduction from trace events to per-layer numbers."""
import pytest

from harness import trace


def _events():
    dev = "/device:TPU:0"
    return {
        "ops": {dev: [
            ["bridge_gather.3", 0, 10],
            ["fusion.12", 5, 10],
            ["copy.1", 20, 10],
            ["fusion.9", 50, 5],                     # after the window
        ]},
        "modules": {dev: [["jit_serve_step(7)", 0, 30]]},
        "host": [
            ["bench:submit", 0, 1],
            ["bench:control", 1, 1],
            ["bench:engine_step", 2, 31],
            ["bench:observe", 33, 7],
        ],
    }


def test_union_clips_and_merges():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (25, 26)], 2, 28) \
        == [[2, 15], [20, 28]]
    assert trace.union_ns([], 0, 1) == []


def test_reduce_busy_kernels_and_gaps():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(25e-9)      # [0,15] + [20,30]
    assert r["steps"] == 1 and r["host_steps"] == 1
    assert r["step_s"] == pytest.approx(30e-9)
    # the bridge_* kernels by name; plain XLA ops are not KV path
    assert r["kv_path_s"] == pytest.approx(10e-9)
    ops = dict(r["device_ops"])
    assert ops == {"bridge_gather": pytest.approx(10e-9),
                   "fusion": pytest.approx(10e-9),
                   "copy": pytest.approx(10e-9)}
    # idle [15, 20] inside the engine's step; [30, 40] in observe
    assert r["idle_gaps"] == [["bench:observe", pytest.approx(10e-9)],
                              ["bench:engine_step", pytest.approx(5e-9)]]


def test_window_is_what_the_device_trace_covers():
    # Two host iterations; the device trace holds only the second one's
    # step: the first iteration is left out, not read as idle.
    dev = "/device:TPU:0"
    ev = {
        "ops": {dev: [["fusion.1", 110, 20]]},
        "modules": {dev: [["jit_serve_step(7)", 105, 30]]},
        "host": [["bench:submit", 0, 1], ["bench:engine_step", 2, 90],
                 ["bench:observe", 92, 8],
                 ["bench:submit", 100, 1], ["bench:engine_step", 102, 36],
                 ["bench:observe", 138, 2]],
    }
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(40e-9)     # [100, 140]
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["steps"] == r["host_steps"] == 1


def test_no_complete_iteration_or_no_device_gives_nothing():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench:observe"]
    assert trace.reduce(ev) is None
    ev = _events()
    ev["ops"] = {}
    assert trace.reduce(ev) is None


def test_hlo_text_names_and_control_flow():
    assert trace.op_name("%bridge_gather.2558 = bf16[8,128,128] custom-call("
                         "s32[8] %x)") == "bridge_gather.2558"
    assert trace.op_name("fusion.3") == "fusion.3"
    ev = _events()
    # a while loop spanning the whole window contains the ops of its body:
    # it neither fills the idle gaps nor tops the breakdown
    ev["ops"]["/device:TPU:0"].append(["while.3", 0, 40])
    r = trace.reduce(ev)
    assert r["busy_s"] == pytest.approx(25e-9)
    assert "while" not in dict(r["device_ops"])


def test_base_name():
    assert trace.base_name("fusion.123") == "fusion"
    assert trace.base_name("bridge_stream_attention.4") == \
        "bridge_stream_attention"
    assert trace.base_name("copy-start") == "copy-start"


def test_recorded_tpu_trace():
    """One loop iteration of a traced granite-3-8b.chat run on a TPU v5e:
    the numbers the reduction gave when it was recorded, and what must
    hold of any trace."""
    import gzip
    import json
    import pathlib
    path = pathlib.Path(__file__).resolve().parent / "fixtures" / \
        "trace_granite_chat.json.gz"
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = trace.reduce(rec["events"])
    for k, v in rec["expect"].items():
        assert r[k] == pytest.approx(v, rel=1e-9), k
    assert r["steps"] == r["host_steps"] == 1
    assert 0 < r["kv_path_s"] < r["step_s"] <= r["busy_s"] <= r["window_s"]
    names = dict(r["device_ops"])
    assert {"bridge_gather", "bridge_stream_attention"} <= set(names)
    assert all(g[0].startswith("bench:") for g in r["idle_gaps"])
