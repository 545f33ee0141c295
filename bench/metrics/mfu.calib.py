"""Operations that the window's calibration steps require
(``bench/lib/flops.calib_step_flops``) over the window times the chip's
bf16 peak."""


def read(ctx):
    lay = ctx["layer"]
    if not lay.get("steps") or lay.get("window_s", 0) <= 0:
        return None
    ops = lay["steps"] * lay["step_ops"]
    return 100.0 * ops / (lay["window_s"] * ctx["peaks"]["bf16_flops"])
