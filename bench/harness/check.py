"""Decide ``correct``: what the timed path served against the reference.

Once the window has closed and the program's state is freed, a sample of
the requests the server finished is drawn from the seed: the longest, the
longest of those admitted into a slot that an earlier request had used (a
slot reset on admit), and others at random, up to the check file's
``requests``.  The reference runs once over each prompt followed by its
served tokens; at every position where a served token was due, the gap
``max(reference logits) - reference logit of the served token`` is read.
The widest gap over the sample, and the mean gap over its served tokens,
are compared with the cell's limits (``bench/checks/<workload>.json``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def bucket(n: int, lo: int = 256) -> int:
    """Padded sequence length of the reference (a few shapes, compiled
    once each)."""
    b = lo
    while b < n:
        b *= 2
    return b


def sample(records, n: int, seed: int) -> List[Any]:
    """Finished requests to compare, drawn from the seed."""
    done = [r for r in records if r.seq is not None and r.seq.done]
    if not done:
        return []
    size = lambda r: r.seq.req.prompt_len + len(r.seq.out)  # noqa: E731
    picked = [max(done, key=size)]
    reused = [r for r in done if r.slot_reused and r is not picked[0]]
    if reused:
        picked.append(max(reused, key=size))
    rest = [r for r in done if all(r is not p for p in picked)]
    rng = np.random.default_rng([abs(int(seed)), 99])
    for i in rng.permutation(len(rest))[: max(n - len(picked), 0)]:
        picked.append(rest[int(i)])
    return picked[:n]


def arrays(picked, n: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """(tokens [n, S], targets [n, S], served tokens compared)."""
    seqs = [(list(r.seq.req.prompt), list(r.seq.out)) for r in picked]
    longest = max(len(p) + len(o) - 1 for p, o in seqs)
    s = bucket(longest)
    tokens = np.zeros((n, s), np.int32)
    targets = np.full((n, s), -1, np.int32)
    count = 0
    for i, (p, o) in enumerate(seqs):
        seq = p + o[:-1]
        tokens[i, : len(seq)] = seq
        targets[i, len(p) - 1: len(p) - 1 + len(o)] = o
        count += len(o)
    return tokens, targets, count


# The numbers a check file may hold a limit for, each at most its limit.
COMPARED = ("max_logit_gap", "mean_logit_gap")


def compare(ref, cfg: Dict[str, Any], weights, records, *, seed: int,
            requests: int, control: bool = False) -> Dict[str, Any]:
    """Gaps of the sampled requests (and of the int8 control on the same
    sample, if asked)."""
    picked = sample(records, requests, seed)
    if not picked:
        return {"compared_tokens": 0, "max_logit_gap": None,
                "mean_logit_gap": None, "requests": 0}
    tokens, targets, count = arrays(picked, requests)
    gap, cgap = ref.logit_gaps(cfg, weights, tokens, targets,
                               control="int8" if control else None)
    out = {"compared_tokens": count, "requests": len(picked),
           "max_logit_gap": float(gap.max()),
           "mean_logit_gap": float(gap.sum() / count),
           "longest_tokens": int(max(r.seq.req.prompt_len + len(r.seq.out)
                                     for r in picked))}
    if cgap is not None:
        out["control_max_logit_gap"] = float(cgap.max())
        out["control_mean_logit_gap"] = float(cgap.sum() / count)
    return out


def decide(check: Dict[str, Any], cmp: Dict[str, Any], prefix: str = ""
           ) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """``(numbers compared, each with its limit; correct)`` for the gaps
    in ``cmp`` (``prefix="control_"``: the control's gaps on the same
    sample) against the cell's check file.  Too few served tokens
    compared is not correct either."""
    checks = {name: {"value": cmp.get(prefix + name),
                     "limit": check[name]["limit"]}
              for name in COMPARED if name in check}
    checks["compared_tokens"] = {"value": cmp["compared_tokens"],
                                 "limit": int(check["compared_tokens_min"])}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for k, c in checks.items() if k != "compared_tokens") \
        and cmp["compared_tokens"] >= checks["compared_tokens"]["limit"]
    return checks, correct
