"""The KV path's share of its roofline: the least time of the KV work the
visible contexts need (their keys and values read once, the new token's
written, attention FLOPs), from lengths and shapes only, over the
``bridge_*`` kernels' device time per step (``kv_path_ms``).  Traced
window only."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["kv_path_s"] or not t.get("iters") or \
            ctx.peaks is None:
        return None
    rf = ctx.roofline
    least = [rf.least_seconds(*rf.kv_work(ctx.cfg, i.visible),
                              ctx.peaks)[0] for i in t["iters"]]
    return 100.0 * (sum(least) / len(least)) / t["kv_path_s"]
