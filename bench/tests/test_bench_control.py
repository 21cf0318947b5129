"""The check separates the program from its control, at a size a test run
can hold: a small granite-shaped model served on the CPU through the same
harness, bridge and batcher as the cells.  On every seed here the program
is correct and the int8 control (the reference from int8 operands, at the
same prompts and served tokens) is not."""
import pytest


@pytest.mark.parametrize("seed", [2, 3, 31])
def test_program_passes_and_int8_control_fails(bench_run, tiny_cell, seed):
    cell = tiny_cell
    from harness import check
    res = bench_run.run_cell(cell, seed=seed, seconds=3.0, trace=False,
                             require_chip=False, control=True)
    c = res["_compare"]
    limit = cell.check["mean_logit_gap"]["limit"]
    assert res["correct"], c
    assert c["compared_tokens"] >= cell.check["compared_tokens_min"]
    assert c["control_mean_logit_gap"] > limit, c
    # the same decision as the run's own, on the control's gaps
    assert not check.decide(cell.check, c, prefix="control_")[1], c
