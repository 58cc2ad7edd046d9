"""Share of the traced window (a few whole training steps, batch making
included) in which no operation ran on the device."""


def read(ctx):
    if ctx["kind"] != "train" or "trace" not in ctx:
        return None
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
