"""KV datapath and kernels: device time per step of the ``bridge_*``
kernels (union of their intervals), traced window.  The kernels only: the
datapath's plain XLA ops (the scan's slices and copies of the stacked
page pools, about 11 ms of granite's step) carry no name of their own in
the trace and are not counted, so work moved out of the kernels into such
ops lowers this without shortening ``step_ms``."""


def read(ctx):
    t = ctx.trace
    if t is None or t["kv_path_s"] is None:
        return None
    return t["kv_path_s"] * 1e3
