"""A run whose timed path is broken underneath reads ``correct`` false:
the harness's look for a chip is skipped, everything else of a run is
driven (weights, server, window, sample, reference), with one fault
planted in the engine after warm-up."""
import functools

import numpy as np
import pytest


def token_altered(engine):
    """Every token is changed where the step produces it."""
    step = engine.step
    vocab = engine.params["embed"].shape[0]

    @functools.wraps(step)
    def altered(tokens, reset=()):
        return (step(tokens, reset) + 1) % vocab
    engine.step = altered


def state_unchanged(engine):
    """The step returns the state it was given: no KV appended, lengths
    never advance."""
    import jax
    plain = jax.jit(engine._step.__wrapped__)

    def stuck(params, state, tokens):
        out, _ = plain(params, state, tokens)
        return out, state
    engine._step = stuck


def half_batch(engine):
    """Only the first half of the slots is computed; the rest emit 0."""
    step = engine.step

    def half(tokens, reset=()):
        out = np.array(step(tokens, reset))
        out[len(out) // 2:] = 0
        return out
    engine.step = half


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch], ids=lambda f: f.__name__)
def test_fault_reads_not_correct(bench_run, tiny_cell, fault):
    cell = tiny_cell
    res = bench_run.run_cell(cell, seed=31, seconds=3.0, trace=False,
                             require_chip=False, engine_hook=fault)
    assert not res["correct"], res["_compare"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        cell.check["max_logit_gap"]["limit"]


def test_unbroken_run_reads_correct(bench_run, tiny_cell):
    res = bench_run.run_cell(tiny_cell, seed=31, seconds=3.0,
                             trace=False, require_chip=False)
    assert res["correct"], res["_compare"]
