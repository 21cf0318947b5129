"""The whole decode step's share of the chip's peak: the least time its
work needs at the peaks (weights read once, visible KV read once, new KV
written; 2 x parameters x active sequences plus attention FLOPs), over the
device time per step.  Traced window only."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["step_s"] or not t.get("iters") or ctx.peaks is None:
        return None
    rf = ctx.roofline
    least = [rf.least_seconds(*rf.step_work(ctx.cfg, i.visible),
                              ctx.peaks)[0] for i in t["iters"]]
    return 100.0 * (sum(least) / len(least)) / t["step_s"]
