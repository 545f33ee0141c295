"""Analog crossbar MVM kernel with ADC quantization (Pallas TPU).

Simulates the analog signal chain of an RIMC macro (paper §II-A /
Fig. 1b) at tile granularity:

  * each K-tile of ``array_rows`` rows is one physical crossbar activation:
    the differential column current ``x_blk @ (G+ - G-)`` is formed in f32
    (the MXU stands in for the analog dot product),
  * the current is digitized by a saturating ``adc_bits`` ADC (round +
    clip to +-(2^(b-1)-1) steps) — quantization noise and saturation are
    faithfully modeled per tile,
  * digitized partial sums accumulate in VMEM scratch across K-tiles
    (digital shift-and-add periphery),
  * the final column scale converts code units back to weight units.

The K block size IS the crossbar height: ``bk == array_rows`` (256 for
the default RramConfig). The ADC step matches core/rram.py::mvm_reference
(full-scale = rows * code_max * x_absmax / (adc_max * 64)) — ref.py is
the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, gp_ref, gn_ref, scale_ref, o_ref, acc_ref,
            *, n_k: int, code_max: int, adc_bits: int, rows: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    # u8 -> i32 -> f32: Mosaic has no direct u8 -> f32 cast; the
    # differential pair is exact in i32 and in f32.
    w = (gp_ref[...].astype(jnp.int32) - gn_ref[...].astype(jnp.int32)).astype(
        jnp.float32
    )
    cur = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    # per-tile ADC: full scale tracks the tile's input magnitude (the DAC
    # reference), matching core/rram.py::mvm_reference exactly.
    adc_max = 2.0 ** (adc_bits - 1) - 1.0
    x_absmax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    step = rows * code_max * x_absmax / (adc_max * 16.0)
    cur = jnp.clip(jnp.round(cur / step), -adc_max, adc_max) * step
    acc_ref[...] += cur

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("code_max", "adc_bits", "bm", "bn", "interpret",
                     "out_dtype"),
)
def crossbar_mvm(
    x: jax.Array,      # (M, K)
    g_pos: jax.Array,  # (K, N) uint8
    g_neg: jax.Array,  # (K, N) uint8
    scale: jax.Array,  # (1, N) f32
    *,
    code_max: int = 255,
    adc_bits: int = 8,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """bk is pinned to the physical array height (RramConfig.array_rows =
    the K tile), so ADC behaviour is bit-accurate w.r.t. the compact
    model. K must be a multiple of 256."""
    bk = 256  # physical crossbar height
    m, k = x.shape
    _, n = g_pos.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(
            _kernel, n_k=n_k, code_max=code_max, adc_bits=adc_bits, rows=bk
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, g_pos, g_neg, scale)
