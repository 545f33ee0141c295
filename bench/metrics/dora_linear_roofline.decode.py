"""Roofline share of the ``dora_linear`` kernel launches inside the
decode programs of the traced window: for each launch, the larger of its
operations over the chip's bf16 peak and its bytes over the HBM bandwidth
(``bench/lib/flops.kernel_cost`` at the launch's padded extents, read from
the op's text in the device trace), summed, over the launches' summed device
time."""
from bench.lib import flops
from bench.lib import trace as T


def read(ctx):
    tr = ctx.get("trace")
    dev = T.first_device(tr) if tr is not None else None
    if dev is None:
        return None
    eng = ctx["mix"]["engine"]
    kinds = T.module_kinds(tr, dev, ctx["spec"]["num_hidden_layers"],
                           eng["max_slots"], eng["max_len"])
    mine = [x for x in T.launches(tr, dev) if kinds.get(x.module) == "decode"]
    share = T.roofline_share(
        mine, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"],
        lambda m, k, n, r, xb, ob: flops.kernel_cost(
            m, k, n, r, x_bytes=xb, out_bytes=ob))
    return None if share is None else 100.0 * share
