"""Device: the runtime's ``peak_bytes_in_use`` on the fullest chip
after the window, in GB."""


def read(ctx):
    b = ctx.device.get("memory_peak_bytes")
    return b / 1e9 if b else None
