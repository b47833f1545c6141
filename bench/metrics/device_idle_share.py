"""Share of the traced stretch with no operation on the device (1 - union
of device-op intervals / stretch)."""


def read(ctx):
    tr = ctx["trace"]
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
