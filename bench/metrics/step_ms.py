"""Decode step: device time per execution of the ``serve_step`` program
in the traced window (profiler trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["step_s"]:
        return None
    return t["step_s"] * 1e3
