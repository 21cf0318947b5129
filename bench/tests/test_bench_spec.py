"""BENCHMARK.json and the files it names hang together."""
import json
import re

import pytest

from harness import spec

BM = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_and_reports_what_it_must(w):
    cell = spec.load_cell(w["name"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
    assert "mean_logit_gap" in cell.check
    for k in ("max_logit_gap", "mean_logit_gap"):
        if k in cell.check:
            c = cell.check[k]
            assert c["lower"] < c["limit"] < c["upper"]
            assert c["upper"] >= 3 * c["lower"]
    assert cell.config["deployment"]["chips"] == w["chips"]
    cell.reference().arch(cell.config)


def test_every_metric_has_a_reader_and_valid_fields():
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_reader(m["name"]))
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}


def test_configs_files_and_reductions():
    for c in BM["configs"]:
        f = spec.load_json(spec.ROOT / c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank", "_size"))
            assert k in f["published"]
        assert any(w["config"] == c["name"] for w in BM["workloads"])
