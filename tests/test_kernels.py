"""Pallas kernels vs pure-jnp oracles (interpret mode), with shape/dtype
sweeps and hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev dependency (requirements-dev.txt): only the
# property tests skip without it — the example-based kernel parity suite
# (the ISSUE 6 regression gate) must run everywhere.
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - environment-dependent
    HAVE_HYPOTHESIS = False

    def given(**kwargs):  # noqa: D103 - stub so decorators still apply
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)
        return deco

    def settings(**kwargs):
        return lambda fn: fn

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

from repro.core import dora, rram
from repro.kernels import autotune, ops, ref
from repro.kernels.dora_linear import dora_linear, dora_linear_gemv
from repro.kernels.crossbar_mvm import crossbar_mvm


def _mk(m, k, n, r, seed=0, drift=0.1, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w = jax.random.normal(k1, (k, n)) * 0.05
    rcfg = rram.RramConfig(relative_drift=drift)
    xw = rram.apply_drift(rram.program(w, rcfg), rcfg, k2)
    ad = dora.init_adapter(
        k3, k, n, dora.AdapterConfig(rank=r), w_base=rram.dequantize(xw)
    )
    ad["lora_b"] = jax.random.normal(k4, (r, n)) * 0.02
    x = (jax.random.normal(k2, (m, k)) * 0.5).astype(dtype)
    return x, xw, ad


@pytest.mark.parametrize(
    "m,k,n,r",
    [
        (128, 128, 128, 4),
        (128, 256, 384, 8),
        (256, 512, 128, 16),
        (128, 128, 256, 64),
    ],
)
def test_dora_linear_vs_oracle_shapes(m, k, n, r):
    x, xw, ad = _mk(m, k, n, r)
    gamma = ops.dora_gamma(xw, ad)
    y = dora_linear(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1).astype(jnp.float32),
        ad["lora_a"], ad["lora_b"], gamma, interpret=True,
    )
    y_ref = ref.dora_linear_ref(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1),
        ad["lora_a"], ad["lora_b"], gamma,
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rimc_linear_wrapper_padding_and_dtypes(dtype):
    # ragged shapes exercise the padding path
    x, xw, ad = _mk(70, 200, 150, 8, dtype=dtype)
    y = ops.rimc_linear(x, xw, ad)
    w = rram.dequantize(xw)
    acfg = dora.AdapterConfig(rank=8)
    merged = dora.merge_magnitude(w, ad, acfg)
    y_ref = dora.adapted_forward(
        x.astype(jnp.float32), w, ad, acfg, merged_norm=merged
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (128, 512, 256)])
def test_crossbar_mvm_vs_tile_oracle(m, k, n):
    """Same tiling, DAC reference and ADC behaviour as the oracle; only
    f32 accumulation-order rounding (~1e-7) may differ across K tiles."""
    x, xw, _ = _mk(m, k, n, 4)
    y = crossbar_mvm(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1).astype(jnp.float32),
        interpret=True,
    )
    y_ref = ref.crossbar_mvm_ref(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1)
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-6
    )


def test_crossbar_mvm_adc_close_to_ideal():
    x, xw, _ = _mk(128, 512, 128, 4)
    y = ops.rimc_mvm_adc(x, xw)
    ideal = x @ rram.dequantize(xw)
    rel = np.abs(np.asarray(y - ideal)) / (np.abs(np.asarray(ideal)).max() + 1e-9)
    assert rel.max() < 0.05


def test_dora_linear_zero_adapter_is_crossbar_matmul():
    x, xw, ad = _mk(128, 128, 128, 4)
    ad["lora_b"] = jnp.zeros_like(ad["lora_b"])
    gamma = jnp.ones((1, 128), jnp.float32)
    y = dora_linear(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1).astype(jnp.float32),
        ad["lora_a"], ad["lora_b"], gamma, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ rram.dequantize(xw)), rtol=1e-4, atol=1e-4
    )


@settings(max_examples=10, deadline=None)
@given(
    mi=st.integers(1, 3), ki=st.integers(1, 3), ni=st.integers(1, 3),
    r=st.sampled_from([4, 8, 16]), seed=st.integers(0, 2 ** 16),
)
def test_property_dora_linear_matches_oracle(mi, ki, ni, r, seed):
    m, k, n = 128 * mi, 128 * ki, 128 * ni
    x, xw, ad = _mk(m, k, n, r, seed=seed)
    gamma = ops.dora_gamma(xw, ad)
    y = dora_linear(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1).astype(jnp.float32),
        ad["lora_a"], ad["lora_b"], gamma, interpret=True,
    )
    y_ref = ref.dora_linear_ref(
        x, xw.g_pos, xw.g_neg, xw.scale.reshape(1, -1),
        ad["lora_a"], ad["lora_b"], gamma,
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# decode-shaped GEMV variant, int8 MMA, autotuner (ISSUE 6)
# ---------------------------------------------------------------------------


def _rimc_ref(x, xw, ad):
    w = rram.dequantize(xw)
    acfg = dora.AdapterConfig(rank=ad["lora_a"].shape[-1])
    merged = dora.merge_magnitude(w, ad, acfg)
    return dora.adapted_forward(
        x.astype(jnp.float32), w, ad, acfg, merged_norm=merged
    )


@pytest.mark.parametrize("m", [1, 2, 8])
@pytest.mark.parametrize("k,n", [(128, 128), (200, 150)])
def test_rimc_linear_decode_shapes_vs_oracle(m, k, n):
    """Small-M calls (the decode hot path) dispatch the GEMV variant —
    incl. ragged K/N where the wrapper pads on TPU and not on CPU."""
    x, xw, ad = _mk(m, k, n, 8)
    y = ops.rimc_linear(x, xw, ad)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_rimc_ref(x, xw, ad)), rtol=1e-4, atol=1e-4
    )


def test_dora_linear_gemv_matches_tiled_kernel():
    """Same operands, same K split: the single-M-block GEMV launcher and
    the tiled launcher compute identical sums."""
    x, xw, ad = _mk(8, 256, 128, 8)
    gamma = ops.dora_gamma(xw, ad)
    scale = xw.scale.reshape(1, -1).astype(jnp.float32)
    xp = jnp.pad(x, ((0, 120), (0, 0)))  # tiled kernel needs M % 128 == 0
    y_tiled = dora_linear(
        xp, xw.g_pos, xw.g_neg, scale, ad["lora_a"], ad["lora_b"], gamma,
        interpret=True,
    )[:8]
    y_gemv = dora_linear_gemv(
        x, xw.g_pos, xw.g_neg, scale, ad["lora_a"], ad["lora_b"], gamma,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(y_gemv), np.asarray(y_tiled), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("m", [2, 128])
def test_rimc_linear_int8_accum_tolerance(m):
    """Integer MMA path: s8 activation quantization bounds the error at
    <2% of the output absmax (codes dequant stays exact — the u8->s8
    offset recode cancels in the differential combine)."""
    x, xw, ad = _mk(m, 256, 128, 8)
    y8 = ops.rimc_linear(x, xw, ad, accum="int8")
    y_ref = np.asarray(_rimc_ref(x, xw, ad))
    err = np.abs(np.asarray(y8) - y_ref).max()
    assert err < 0.02 * np.abs(y_ref).max() + 1e-6


@settings(max_examples=10, deadline=None)
@given(
    m=st.sampled_from([2, 70, 128]),
    kn=st.sampled_from([(128, 128), (200, 150)]),
    tiles=st.sampled_from([(None, None, None), (128, 128, 128), (8, 64, 32)]),
    seed=st.integers(0, 2 ** 16),
)
def test_property_rimc_linear_block_size_invariant(m, kn, tiles, seed):
    """The output must not depend on tile choice: explicit (bm, bn, bk)
    overrides agree with the autotuned plan (operands pad to any
    choice)."""
    k, n = kn
    x, xw, ad = _mk(m, k, n, 8, seed=seed)
    bm, bn, bk = tiles
    y = ops.rimc_linear(x, xw, ad, bm=bm, bn=bn, bk=bk)
    y_auto = ops.rimc_linear(x, xw, ad)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_auto), rtol=1e-4, atol=1e-5
    )


def test_autotune_interpret_plans_never_pad():
    for m, k, n, r in [(1, 64, 64, 4), (2, 200, 150, 8), (128, 256, 384, 16)]:
        plan = autotune.select_tiles(m, k, n, r, interpret=True)
        assert (plan.m_pad, plan.k_pad, plan.n_pad) == (m, k, n)
        assert plan.gemv  # grid has no M axis: whole M is one block


def test_autotune_tpu_plans_aligned_and_within_budget():
    for m, k, n, r, int8 in [
        (2, 2048, 4096, 8, False), (512, 4096, 4096, 16, False),
        (8, 1024, 1024, 8, True),
    ]:
        plan = autotune.select_tiles(m, k, n, r, interpret=False, int8=int8)
        sublane = 32 if int8 else 8
        assert plan.bm % sublane == 0 and plan.bn % 128 == 0
        assert plan.k_pad % plan.bk == 0 and plan.n_pad % plan.bn == 0
        assert plan.m_pad % plan.bm == 0
        assert autotune._vmem_bytes(
            plan.bm, plan.bn, plan.bk, r, int8
        ) <= autotune.VMEM_BUDGET_BYTES
        if m <= autotune.GEMV_MAX_M:
            assert plan.gemv and plan.bm < 128


@pytest.mark.parametrize("backend,want", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_default_interpret_per_backend(monkeypatch, backend, want):
    """Interpret mode on a CPU host, Mosaic on a TPU, and no silent
    interpreter fallback on any other backend."""
    from repro.substrate import exec as X

    monkeypatch.setattr(X.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            X.default_interpret()
    else:
        assert X.default_interpret() is want
