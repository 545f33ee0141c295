"""Share of the engine's busy time in the window spent outside its batched
decode steps (admission: prefill chunks, first tokens, slot writes): the
harness's clock around every ``submit``/``step`` call, less the engine's
``decode_seconds`` counter over the same window."""


def read(ctx):
    lay = ctx["layer"]
    busy = lay.get("busy_s")
    if not busy:
        return None
    return 100.0 * (busy - lay["decode_s"]) / busy
