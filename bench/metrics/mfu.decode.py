"""Model operations of the tokens decoded in the window
(``bench/lib/flops.decode_flops`` at each token's position) over the
engine's ``decode_seconds`` in the window times the chip's bf16 peak."""


def read(ctx):
    lay = ctx["layer"]
    t = lay.get("decode_s", 0.0)
    if t <= 0 or not lay.get("decode_ops"):
        return None
    return 100.0 * lay["decode_ops"] / (t * ctx["peaks"]["bf16_flops"])
