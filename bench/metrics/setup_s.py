"""Set-up: process start to the first measured step (weights made on the
device, server built, every program of the window compiled or loaded)."""


def read(ctx):
    return ctx.setup_s
