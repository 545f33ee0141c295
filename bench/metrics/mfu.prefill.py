"""Model operations of the prompts whose first token came in the window
(``bench/lib/flops.prefill_flops``) over the engine's time outside decode
steps in the window times the chip's bf16 peak."""


def read(ctx):
    lay = ctx["layer"]
    t = lay.get("busy_s", 0.0) - lay.get("decode_s", 0.0)
    if t <= 0 or not lay.get("prefill_ops"):
        return None
    return 100.0 * lay["prefill_ops"] / (t * ctx["peaks"]["bf16_flops"])
