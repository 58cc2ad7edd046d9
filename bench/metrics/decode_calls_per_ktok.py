"""Host round trips of the serving loop per 1000 generated tokens: each
``decode_calls`` is one exit of the jitted decode loop to the host (a slot
finished), during which the device waits. Counted by the engine's
``EngineStats`` over the untraced window."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["window"]["tokens"]:
        return None
    w = ctx["window"]
    return 1000.0 * w["stats"]["decode_calls"] / w["tokens"]
