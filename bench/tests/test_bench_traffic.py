"""The copied generator: the same work for every seed."""
import numpy as np
import pytest

from harness import spec, traffic


def _mix(name):
    return spec.load_json(spec.BENCH / "traffic" / f"{name}.json")


def _sizes(arr):
    return sorted((a.tenant, len(a.prompt), a.output_len) for a in arr)


@pytest.mark.parametrize("name,seconds", [("chat", 45.0),
                                          ("codebatch", 45.0)])
def test_deterministic_per_seed_and_same_work_across_seeds(name, seconds):
    mix = _mix(name)
    kw = dict(seconds=seconds, vocab=49152, max_len=2048)
    a = traffic.generate(mix, seed=2 ** 31 + 12345, **kw)
    b = traffic.generate(mix, seed=2 ** 31 + 12345, **kw)
    c = traffic.generate(mix, seed=7, **kw)
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert _sizes(a) == _sizes(c)                      # same multiset
    if mix.get("order") == "fixed":
        # the same requests in the same order for every seed
        assert [(x.tenant, len(x.prompt), x.output_len) for x in a] == \
            [(x.tenant, len(x.prompt), x.output_len) for x in c]
    else:
        assert [x.output_len for x in a] != [x.output_len for x in c]
        assert sorted(x.due_s for x in a) != sorted(x.due_s for x in c)
    assert all(1 <= t < 49152 for x in a for t in x.prompt)


def test_chat_matches_stated_means_caps_and_rate():
    mix = _mix("chat")
    arr = traffic.generate(mix, seed=3, seconds=45.0, vocab=49155,
                           max_len=2048)
    rate = mix["rate_per_s"]
    assert len(arr) == int(rate * 45.0)
    assert max(a.due_s for a in arr) <= 45.0
    assert arr[-1].due_s == pytest.approx(len(arr) / rate)
    for t in mix["tenants"]:
        mine = [a for a in arr if a.tenant == t["name"]]
        assert abs(len(mine) - len(arr) / len(mix["tenants"])) <= 1
        for part, vals in (("prompt", [len(a.prompt) for a in mine]),
                           ("output", [a.output_len for a in mine])):
            p = t[part]
            assert 1 <= min(vals) and max(vals) <= p["max"]
            # bounded Lomax at fixed quantiles: within 15 % of the stated
            # mean (the cap and the floor pull it down a little)
            assert np.mean(vals) == pytest.approx(p["mean"], rel=0.15)


def test_quantile_lengths_match_the_programs_heavy_len():
    """The copy's arithmetic is the program's: same draws, same lengths."""
    from repro.serve.traffic import _heavy_len
    rng = np.random.default_rng(5)
    u = rng.random(2000)
    for mean, tail, cap in ((24, 2.5, 256), (256, 2.5, 1024)):
        # a Pareto-II draw of shape a is (1 - u) ** (-1 / a) - 1
        ours = traffic.heavy_len_quantile(u, mean, tail, cap)

        class Fixed:
            def __init__(self, vals):
                self.vals = iter(vals)

            def pareto(self, a):
                return (1 - next(self.vals)) ** (-1 / a) - 1
        fx = Fixed(u)
        theirs = [_heavy_len(fx, mean, tail, cap) for _ in u]
        assert list(ours) == theirs


def test_stratified_order_spreads_every_block():
    rng = np.random.default_rng(0)
    order = traffic.stratified_order(64, 8, rng)
    assert sorted(order) == list(range(64))
    for b in range(8):
        block = order[b * 8:(b + 1) * 8]
        assert sorted(r // 8 for r in block) == list(range(8))


def test_backlog_is_due_at_once_and_fits_max_len():
    mix = _mix("codebatch")
    arr = traffic.generate(mix, seed=11, seconds=45.0, vocab=49152,
                           max_len=2048)
    assert len(arr) == mix["requests"]
    assert all(a.due_s == 0.0 for a in arr)
    assert all(len(a.prompt) + a.output_len <= 2048 for a in arr)
    # each block of 8 (the 8 slots' first fill, then each refill) carries
    # one request from every octile of prompt + output length
    total = [len(a.prompt) + a.output_len for a in arr]
    rank = {i: r for r, i in enumerate(np.argsort(total, kind="stable"))}
    for b in range(8):
        octiles = sorted(rank[i] // 8 for i in range(b * 8, b * 8 + 8))
        assert octiles == list(range(8))


def test_prompt_plus_output_over_max_len_is_refused():
    mix = _mix("codebatch")
    with pytest.raises(ValueError):
        traffic.generate(mix, seed=1, seconds=45.0, vocab=100, max_len=512)
