"""Share of the traced steady window in which no operation ran on the
device (1 - union of device-op intervals / window), serving cells."""


def read(ctx):
    if ctx["kind"] != "serve" or "trace" not in ctx:
        return None
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
