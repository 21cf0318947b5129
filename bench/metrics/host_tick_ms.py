"""Front end and control plane: host time per loop iteration outside the
engine's step (submit, control, step_inputs, observe), the whole window's
total over its iterations."""


def read(ctx):
    it = [i for i in ctx.window.iters if i.end_s <= ctx.seconds]
    if not it:
        return None
    host = sum((i.end_s - i.start_s) - (i.step_end_s - i.step_start_s)
               for i in it)
    return host / len(it) * 1e3
