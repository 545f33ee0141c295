"""Jit'd execution wrappers around the Pallas kernels (absorbed from
``kernels/ops.py``; that module re-exports these names for
backward compatibility).

``rimc_linear`` is the deployment-path op: it takes a CrossbarWeight (the
programmed+drifted RRAM array), the DoRA adapter, and the merged column
norms, picks block sizes with the analytic tuner
(``kernels/autotune.py``), pads operands to the planned tiles, and
dispatches the fused kernel — the decode-shaped GEMV variant when the
whole (small) M fits one block, the tiled kernel otherwise. On a CPU
host ``interpret=True`` executes the kernel body with jnp semantics (and
the tuner plans unpadded tiles); on TPU the same call compiles to
Mosaic. The serving hot path hoists the static operand padding out of
this per-call wrapper entirely — see ``substrate/prepared.py``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dora as dora_lib
from repro.core.rram import CrossbarWeight, dequantize
from repro.kernels import autotune
from repro.kernels.dora_linear import dora_linear, dora_linear_gemv
from repro.kernels.crossbar_mvm import crossbar_mvm


def default_interpret() -> bool:
    """Pallas interpret mode on a CPU host (tests, CPU runs), Mosaic on a
    TPU. Any other backend has no lowering for these kernels and raises
    rather than falling back to the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the crossbar kernels run on a TPU (Mosaic) or a CPU host "
        f"(interpret mode); JAX's default backend is {backend!r}"
    )


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def code_column_norms(xw: CrossbarWeight) -> jax.Array:
    """Per-output-column L2 norms of the resident codes, read back
    digitally: shape ``(..., n)`` for codes of shape ``(..., k, n)``.

    Stacked-codes dispatch: the reduction is over the row axis (-2), so
    ANY leading stacking works unchanged — a fleet's chip axis, expert
    stacks, scan-group stacks, or combinations. This is the cheap
    forward-free signal the fleet's drift proxy monitors: conductance
    relaxation perturbs the very column norms the DoRA merge (Algorithm
    2 line 12) divides by, so their relative movement since the last
    calibration tracks how stale the merged γ has become.
    """
    w = dequantize(xw)
    return jnp.sqrt(jnp.sum(w * w, axis=-2))


def faulted_view(xw: CrossbarWeight, leaf_faults, cfg) -> CrossbarWeight:
    """The faulty read-back view of one leaf's codes: retention decay,
    I-V read distortion, saturation clamps and stuck pins applied on the
    code grid (``repro/faults/map.py``), per-column scale untouched.

    This is the single-leaf read-back choke point of the non-ideality
    suite: the pristine resident codes are NEVER mutated — drift keeps
    operating on them — and every consumer (all three backends, the
    prepared/fused serve path, the fleet's drift proxy) reads the view
    this function derives, so backend parity under faults is bitwise by
    construction. ``leaf_faults=None`` is the healthy identity."""
    if leaf_faults is None:
        return xw
    return leaf_faults.apply(xw, cfg)


def faulted_codes(tree, fault_map, cfg):
    """Tree-level ``faulted_view``: derive the faulty codes view of a
    whole base tree through a composed ``FaultMap`` (``None`` = healthy,
    returns the tree unchanged). ``Deployment._refresh_base`` /
    ``Fleet._refresh_base`` call this after every programming, drift or
    injection event; ``prepare_base_for_serve(faults=...)`` routes the
    serve-time fast path through the same derivation."""
    if fault_map is None:
        return tree
    from repro.faults.map import apply_fault_map

    return apply_fault_map(tree, fault_map, cfg)


def dora_gamma(xw: CrossbarWeight, adapter: dict) -> jax.Array:
    """Merged DoRA scale M/||W_r + A@B|| (Algorithm 2 line 12), shape (1,N)."""
    w = dequantize(xw)
    norm = dora_lib.column_norm(w, adapter["lora_a"], adapter["lora_b"])
    m = adapter["dora_m"].astype(jnp.float32)
    return (m / norm)[None, :]


def rimc_linear(
    x: jax.Array,
    xw: CrossbarWeight,
    adapter: dict,
    gamma: Optional[jax.Array] = None,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
    accum: str = "f32",
) -> jax.Array:
    """Fused Y = gamma * (X W_r + (XA)B) with autotuned tile selection.
    x: (..., K) — leading dims flattened to M. Block sizes default to the
    analytic plan for (M, K, N, r) (``kernels/autotune.py``); explicit
    ``bm``/``bn``/``bk`` override it (operands pad up to any choice, so
    the output is block-size invariant — pinned by a hypothesis test).
    ``accum="int8"`` selects the integer MMA path; ``interpret`` defaults
    to ``default_interpret()``."""
    if interpret is None:
        interpret = default_interpret()
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = xw.g_pos.shape[-1]
    r = adapter["lora_a"].shape[-1]
    if gamma is None:
        gamma = dora_gamma(xw, adapter)
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    if bm is None or bn is None or bk is None:
        plan = autotune.select_tiles(
            m, k, n, r, interpret=interpret, int8=(accum == "int8")
        )
        bm = plan.bm if bm is None else bm
        bn = plan.bn if bn is None else bn
        bk = plan.bk if bk is None else bk
    xf = _pad_to(_pad_to(xf, bm, 0), bk, 1)
    gp = _pad_to(_pad_to(xw.g_pos, bk, 0), bn, 1)
    gn = _pad_to(_pad_to(xw.g_neg, bk, 0), bn, 1)
    scale = _pad_to(xw.scale.reshape(1, -1).astype(jnp.float32), bn, 1)
    a = _pad_to(adapter["lora_a"].astype(jnp.float32), bk, 0)
    b = _pad_to(adapter["lora_b"].astype(jnp.float32), bn, 1)
    g = _pad_to(gamma.astype(jnp.float32), bn, 1)
    if xf.shape[0] == bm:
        # decode-shaped: single M block, K-parallel grid only
        y = dora_linear_gemv(
            xf, gp, gn, scale, a, b, g,
            bn=bn, bk=bk, interpret=interpret, accum=accum,
        )
    else:
        y = dora_linear(
            xf, gp, gn, scale, a, b, g,
            bm=bm, bn=bn, bk=bk, interpret=interpret, accum=accum,
        )
    return y[:m, :n].reshape(lead + (n,)).astype(x.dtype)


def rimc_mvm_adc(
    x: jax.Array,
    xw: CrossbarWeight,
    *,
    code_max: int = 255,
    adc_bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """ADC-faithful crossbar MVM (no adapter): analog fidelity studies."""
    if interpret is None:
        interpret = default_interpret()
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = xw.g_pos.shape[-1]
    xf = x.reshape(-1, k)
    m = xf.shape[0]
    xf = _pad_to(_pad_to(xf, bm, 0), 256, 1)
    gp = _pad_to(_pad_to(xw.g_pos, 256, 0), bn, 1)
    gn = _pad_to(_pad_to(xw.g_neg, 256, 0), bn, 1)
    scale = _pad_to(xw.scale.reshape(1, -1).astype(jnp.float32), bn, 1)
    y = crossbar_mvm(
        xf, gp, gn, scale, code_max=code_max, adc_bits=adc_bits,
        bm=bm, bn=bn, interpret=interpret,
    )
    return y[:m, :n].reshape(lead + (n,)).astype(x.dtype)
