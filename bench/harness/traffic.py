"""Traffic mixes: one general generator over the mix files.

A mix file (``bench/traffic/<name>.json``) gives the arrival process and
each tenant's length distributions::

    {"arrivals": "poisson", "rate_per_s": 2.8,     # open loop, wall time
     "tenants": [{"name": "chat", "qos": "interactive", "share": 3.0,
                  "prompt": {"mean": 24, "tail": 2.5, "max": 256},
                  "output": {"mean": 16, "tail": 2.5, "max": 128}}, ...]}

or ``"arrivals": "backlog", "requests": 64`` (every request due at the
window's start).  ``"order": "fixed"`` keeps the base order below for
every seed; the default, ``"seeded"``, permutes it by the seed.  The tenants send at equal rates; ``share`` is the
scheduler's share of slots.  ``"follow"`` says how long a run goes on
after the window closes, with no new arrivals: ``"first_token"`` until
every request due in the window has its first token, ``"none"`` not at
all.

Lengths are bounded Lomax draws, the arithmetic of the program's
``repro.serve.traffic._heavy_len`` (``1 + floor(mean * (tail - 1) * X)``
for a Pareto-II ``X`` of shape ``tail``, clipped to ``[1, max]``), copied
here so that the yardstick does not move with the program.

Every seed gets the same work.  A run's request count,
lengths and inter-arrival gaps are fixed quantiles of their
distributions, and each request's pair of prompt and output length is
fixed too.  The order is stratified (the sorted values fall into
``STRATA`` bands and every consecutive block of ``STRATA`` requests takes
at most one value from each band), fixed as a base order, and the seed
only permutes within each block; the seed also draws the prompt tokens
(one generator per ``(seed, tenant, index)``, as the program's generator
seeds per tenant and step).  So two seeds offer the same load over every
stretch of the window and differ in local order and in token ids.
Under ``"order": "fixed"`` the seeds differ in token ids alone: a
backlog that the window closes partway through is all due at once, so
its order is the work the window holds, and a seeded order would count
whichever prompts the seed put before the close (in a queue model of
the code batch, with 8 slots and a fixed step, within-block orders
spread the window's output tokens by 1.5 % over 24 seeds).  (A
plain permutation of the same requests and gaps lets the seed bunch long
prompts or short gaps together: in a queue model of the chat cell at
3.0 req/s, with 8 slots and a fixed 37 ms step, the TTFT p90 of 48 seeds
spreads 14 % that way against 4.7 % stratified, and 36 % with
independent Poisson draws.)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

# Bands of the stratified order, and the length of the blocks the seed
# permutes within: the batcher's slot count in both cells.
STRATA = 8


def heavy_len_quantile(u: np.ndarray, mean: float, tail: float,
                       cap: int) -> np.ndarray:
    """Bounded Lomax lengths at quantiles ``u`` in (0, 1)."""
    x = (1.0 - np.asarray(u, np.float64)) ** (-1.0 / tail) - 1.0
    body = mean * (tail - 1.0) * x
    return np.clip(1 + np.floor(body), 1, max(int(cap), 1)).astype(np.int64)


def quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def block_shuffle(order: np.ndarray, block: int,
                  rng: np.random.Generator) -> np.ndarray:
    """``order`` with each run of ``block`` consecutive entries permuted."""
    out = np.array(order)
    for i in range(0, len(out), block):
        out[i:i + block] = rng.permutation(out[i:i + block])
    return out


def stratified_order(n: int, strata: int,
                     rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` (ranks of sorted values) in which each
    block of ``strata`` consecutive entries holds at most one rank from
    each of ``strata`` equal bands."""
    strata = max(1, min(strata, n))
    band = (np.arange(n) * strata) // n
    block = np.empty(n, np.int64)
    for b in range(strata):
        members = np.flatnonzero(band == b)
        block[rng.permutation(members)] = np.arange(len(members))
    tie = rng.random(n)
    return np.lexsort((tie, block))


@dataclass(frozen=True)
class Arrival:
    """One request of a run: when it is due and what it asks for."""

    index: int
    tenant: str
    due_s: float
    prompt: tuple
    output_len: int


def generate(mix: Dict[str, Any], *, seed: int, seconds: float,
             vocab: int, max_len: int) -> List[Arrival]:
    """The run's requests, sorted by due time."""
    tenants = mix["tenants"]
    if mix["arrivals"] == "poisson":
        rate = float(mix["rate_per_s"])
        n = int(np.floor(rate * seconds))
    elif mix["arrivals"] == "backlog":
        n = int(mix["requests"])
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    # The base order of tenants, gaps and requests is the same for every
    # seed (a fixed generator); the seed permutes within blocks of
    # ``STRATA``, so every seed offers the same load over the same
    # stretches of the window, or, under ``"order": "fixed"``, nothing.
    base = np.random.default_rng([0, 0])
    rng = np.random.default_rng([abs(int(seed)), 0])
    seeded = mix.get("order", "seeded") == "seeded"
    if not seeded and mix["order"] != "fixed":
        raise ValueError(f"unknown order {mix['order']!r}")

    def shuffle(o: np.ndarray, block: int) -> np.ndarray:
        return block_shuffle(o, block, rng) if seeded else o

    def order(m: int) -> np.ndarray:
        return shuffle(stratified_order(m, STRATA, base), STRATA)

    counts = np.full(len(tenants), n // len(tenants))
    counts[: n - counts.sum()] += 1
    owner = np.repeat(np.arange(len(tenants)), counts)
    owner = owner[shuffle(stratified_order(n, len(tenants), base),
                          len(tenants))]

    if mix["arrivals"] == "poisson":
        # Exponential gaps at fixed quantiles, scaled so that the n-th
        # request is due at n / rate.
        gaps = -np.log(1.0 - quantile_grid(n))
        gaps = gaps[order(n)]
        due = np.cumsum(gaps) / gaps.sum() * (n / rate)
    else:
        due = np.zeros(n)

    out: List[Arrival] = []
    for ti, t in enumerate(tenants):
        idx = np.flatnonzero(owner == ti)
        m = len(idx)
        if m == 0:
            continue
        # The pairing of prompt and output lengths is fixed (the same
        # requests for every seed); the seed orders whole requests.
        fixed = np.random.default_rng([ti, 1])
        plen = heavy_len_quantile(quantile_grid(m), t["prompt"]["mean"],
                                  t["prompt"]["tail"], t["prompt"]["max"])
        olen = heavy_len_quantile(quantile_grid(m), t["output"]["mean"],
                                  t["output"]["tail"], t["output"]["max"])
        olen = olen[stratified_order(m, STRATA, fixed)]
        by_total = np.argsort(plen + olen, kind="stable")
        pick = by_total[order(m)]
        lens = {"prompt": plen[pick], "output": olen[pick]}
        if np.any(lens["prompt"] + lens["output"] > max_len):
            raise ValueError(f"tenant {t['name']}: prompt + output exceeds "
                             f"max_len {max_len}")
        for j, i in enumerate(idx):
            r = np.random.default_rng([abs(int(seed)), ti + 1, j])
            prompt = tuple(int(x) for x in
                           r.integers(1, vocab, size=int(lens["prompt"][j])))
            out.append(Arrival(index=int(i), tenant=t["name"],
                               due_s=float(due[i]), prompt=prompt,
                               output_len=int(lens["output"][j])))
    out.sort(key=lambda a: (a.due_s, a.index))
    return out
