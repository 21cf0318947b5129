"""Device memory: ``peak_bytes_in_use`` of the fullest chip after the
window, in GB (1e9 bytes)."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 1e9
