"""Put the benchmark's harness and the program on the path for its tests;
load ``bench/run.py`` as a module."""
import importlib.util
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def bench_run():
    spec = importlib.util.spec_from_file_location("bench_run_module",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tiny_cell():
    """A small granite-shaped model served on the CPU through the same
    harness, bridge and batcher as the cells
    (``fixtures/tiny-granite*.json``, ``fixtures/tiny-mix.json``)."""
    from harness import spec
    fix = BENCH / "tests" / "fixtures"
    return spec.Cell(
        name="tiny-granite.chat", chips=1,
        config=spec.load_json(fix / "tiny-granite.json"),
        traffic=spec.load_json(fix / "tiny-mix.json"),
        check=spec.load_json(fix / "tiny-granite.check.json"),
        end_to_end=[], per_layer=[])
