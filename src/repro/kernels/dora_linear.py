"""Fused RIMC-DoRA linear kernels (Pallas TPU).

Computes, in one pass over the crossbar codes (paper eq. 2 + eq. 6):

    Y = (X @ W_r + (X @ A) @ B) * gamma
    W_r = (G+ - G-) * scale          (differential int8 conductance pair)
    gamma = M / ||W_r + A@B||_col    (DoRA magnitude / merged column norm)

Two launchers over the same kernel bodies:

* ``dora_linear`` — prefill-shaped: grid (M/bm, N/bn, K/bk), K innermost
  so the accumulators live in VMEM scratch across the K loop (MXU-aligned
  tiles at full size).
* ``dora_linear_gemv`` — decode-shaped: M is a single sublane-aligned
  block (a handful of active slots), the grid is (N/bn, K/bk) with the
  K-parallel accumulator reduction only. No M axis means no 128-row pad
  of a 2-row decode batch (ISSUE 6 tentpole 1).

Both take ``accum``:

* ``"f32"``  — codes are dequantized in-register per tile
  ((G+ - G-) as f32) and accumulated on the MXU in f32.
* ``"int8"`` — integer MMA: x is quantized per-row to s8, codes are
  offset-recoded u8 -> s8 (``g - 128``; the offsets cancel exactly in the
  differential combine, so the integer dot of the recoded pair equals
  ``x_q @ (G+ - G-)``), both dots run with
  ``preferred_element_type=jnp.int32``, and the per-row x scale plus the
  per-column code scale fold into the f32 epilogue together with the
  low-rank path.

The low-rank path rides the same K loop: per K-tile we accumulate XA
(bm, r) — r is tiny (4..64) — and the last K step applies (XA)@B and the
DoRA scale. ``gamma`` is precomputed at merge time (Algorithm 2 line 12)
by ``ops.dora_gamma``; tile selection lives in ``kernels/autotune.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, gp_ref, gn_ref, scale_ref, a_ref, b_ref, gamma_ref,
            o_ref, acc_ref, xa_ref, *, n_k: int, k_axis: int):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    # in-register differential dequant: u8 codes -> f32 weights. Mosaic
    # has no direct u8 -> f32 cast, so the pair widens to i32 first; the
    # difference (|G+ - G-| <= 255) is exact in i32 and in f32.
    w = (gp_ref[...].astype(jnp.int32) - gn_ref[...].astype(jnp.int32)).astype(
        jnp.float32
    )
    acc_ref[...] += jax.lax.dot(
        x.astype(jnp.float32), w, preferred_element_type=jnp.float32
    )
    xa_ref[...] += jax.lax.dot(
        x.astype(jnp.float32), a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = scale_ref[...]  # (1, bn) per-column code scale
        lowrank = jax.lax.dot(
            xa_ref[...], b_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        y = acc_ref[...] * scale + lowrank
        o_ref[...] = (y * gamma_ref[...]).astype(o_ref.dtype)


def _kernel_int8(x_ref, xs_ref, gp_ref, gn_ref, scale_ref, a_ref, b_ref,
                 gamma_ref, o_ref, acc_ref, xa_ref, *, n_k: int, k_axis: int):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    xq = x_ref[...]  # s8, rows scaled by xs
    # integer MMA on the recoded differential pair: the -128 offsets of
    # g_pos/g_neg cancel, so this int32 sum is exactly x_q @ (G+ - G-).
    acc_ref[...] += jax.lax.dot(
        xq, gp_ref[...], preferred_element_type=jnp.int32
    ) - jax.lax.dot(xq, gn_ref[...], preferred_element_type=jnp.int32)
    xa_ref[...] += jax.lax.dot(
        xq.astype(jnp.float32), a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        xs = xs_ref[...]  # (bm, 1) per-row x quantization scale
        lowrank = jax.lax.dot(
            xa_ref[...] * xs, b_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        y = acc_ref[...].astype(jnp.float32) * xs * scale_ref[...] + lowrank
        o_ref[...] = (y * gamma_ref[...]).astype(o_ref.dtype)


def _quantize_rows(x: jax.Array):
    """Per-row symmetric s8 quantization: x ~= x_q * xs (xs f32 (M, 1))."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    xs = jnp.maximum(absmax, 1e-30) / 127.0
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    return xq, xs


def recode_s8(g: jax.Array) -> jax.Array:
    """Offset recode u8 codes to s8 (``g - 128``). Exact for the
    differential pair: the offsets cancel in ``(G+ - 128) - (G- - 128)``."""
    if g.dtype == jnp.int8:
        return g
    return (g.astype(jnp.int16) - 128).astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "out_dtype", "accum"),
)
def dora_linear(
    x: jax.Array,       # (M, K)
    g_pos: jax.Array,   # (K, N) uint8 (or s8 when pre-recoded)
    g_neg: jax.Array,   # (K, N) uint8 (or s8 when pre-recoded)
    scale: jax.Array,   # (1, N) f32 — code->weight scale per column
    a: jax.Array,       # (K, r)
    b: jax.Array,       # (r, N)
    gamma: jax.Array,   # (1, N) f32 — merged DoRA magnitude M/||.||
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
    accum: str = "f32",
):
    m, k = x.shape
    _, n = g_pos.shape
    r = a.shape[1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    operand_specs = [
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # g_pos
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # g_neg
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),     # scale
        pl.BlockSpec((bk, r), lambda i, j, kk: (kk, 0)),    # a
        pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),     # b
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),     # gamma
    ]
    if accum == "int8":
        xq, xs = _quantize_rows(x)
        kernel = functools.partial(_kernel_int8, n_k=n_k, k_axis=2)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),  # x_q
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),    # x row scale
        ] + operand_specs
        acc_dtype = jnp.int32
        args = (xq, xs, recode_s8(g_pos), recode_s8(g_neg))
    else:
        assert accum == "f32", accum
        kernel = functools.partial(_kernel, n_k=n_k, k_axis=2)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),  # x
        ] + operand_specs
        acc_dtype = jnp.float32
        args = (x, g_pos, g_neg)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), acc_dtype),    # main accumulator
            pltpu.VMEM((bm, r), jnp.float32),   # low-rank XA accumulator
        ],
        interpret=interpret,
    )(*args, scale, a, b, gamma)


@functools.partial(
    jax.jit,
    static_argnames=("bn", "bk", "interpret", "out_dtype", "accum"),
)
def dora_linear_gemv(
    x: jax.Array,       # (M, K), M small (one decode batch) — no M grid
    g_pos: jax.Array,   # (K, N)
    g_neg: jax.Array,   # (K, N)
    scale: jax.Array,   # (1, N)
    a: jax.Array,       # (K, r)
    b: jax.Array,       # (r, N)
    gamma: jax.Array,   # (1, N)
    *,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
    accum: str = "f32",
):
    """Decode-shaped variant: the whole (small) M is one block and the
    grid is (N/bn, K/bk) with K innermost — the accumulator reduction
    without the M axis, so a 2-row decode tick never pads to 128 rows."""
    m, k = x.shape
    _, n = g_pos.shape
    r = a.shape[1]
    assert n % bn == 0 and k % bk == 0, (m, n, k, bn, bk)
    n_k = k // bk
    grid = (n // bn, n_k)
    operand_specs = [
        pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),   # g_pos
        pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),   # g_neg
        pl.BlockSpec((1, bn), lambda j, kk: (0, j)),     # scale
        pl.BlockSpec((bk, r), lambda j, kk: (kk, 0)),    # a
        pl.BlockSpec((r, bn), lambda j, kk: (0, j)),     # b
        pl.BlockSpec((1, bn), lambda j, kk: (0, j)),     # gamma
    ]
    if accum == "int8":
        xq, xs = _quantize_rows(x)
        kernel = functools.partial(_kernel_int8, n_k=n_k, k_axis=1)
        in_specs = [
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),  # x_q
            pl.BlockSpec((m, 1), lambda j, kk: (0, 0)),    # x row scale
        ] + operand_specs
        acc_dtype = jnp.int32
        args = (xq, xs, recode_s8(g_pos), recode_s8(g_neg))
    else:
        assert accum == "f32", accum
        kernel = functools.partial(_kernel, n_k=n_k, k_axis=1)
        in_specs = [
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),  # x
        ] + operand_specs
        acc_dtype = jnp.float32
        args = (x, g_pos, g_neg)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((m, bn), acc_dtype),     # main accumulator
            pltpu.VMEM((m, r), jnp.float32),    # low-rank XA accumulator
        ],
        interpret=interpret,
    )(*args, scale, a, b, gamma)
