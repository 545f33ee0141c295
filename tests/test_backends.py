"""Substrate backend parity: the whole zoo on resident uint8 codes.

Contract (ISSUE 1 acceptance): for one drifted deployment, the ``codes``
backend (fused Pallas kernel, interpret mode on CPU) and the ``dequant``
backend agree to programming-quantization tolerance end-to-end through
``launch/serve.py``, and ``rram_bytes`` is a real measurement of the
resident code arrays under codes mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import substrate
from repro.configs import get_arch
from repro.core import calibrate as C
from repro.core import dora, rram
from repro.launch import serve
from repro.models import transformer as T


def _programmed_pair(arch_id, seed=0):
    """Same programming event in both substrate representations."""
    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    codes = C.program_model(params["base"], cfg.rram, key, mode="codes")
    floats = C.program_model(params["base"], cfg.rram, key, mode="dequant")
    return cfg, params, codes, floats


# -- registry ----------------------------------------------------------------


def test_backend_registry_and_context():
    assert set(substrate.available_backends()) >= {
        "dequant", "codes", "codes_adc"
    }
    assert substrate.active_backend_name() == substrate.DEFAULT_BACKEND
    with substrate.use_backend("codes_adc"):
        assert substrate.active_backend_name() == "codes_adc"
    assert substrate.active_backend_name() == substrate.DEFAULT_BACKEND
    with pytest.raises(KeyError):
        substrate.get_backend("analog_dreams")
    with pytest.raises(KeyError):
        with substrate.use_backend("analog_dreams"):
            pass


# -- single-linear parity ----------------------------------------------------


def _linear_fixture(d=200, n=150, r=8, seed=0):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (d, n)) * 0.05
    rcfg = rram.RramConfig(relative_drift=0.1)
    xw = rram.programmed_codes(w, rcfg, jax.random.fold_in(key, 1))
    acfg = dora.AdapterConfig(rank=r)
    ad = dora.init_adapter(
        jax.random.fold_in(key, 2), d, n, acfg, w_base=rram.dequantize(xw)
    )
    ad["lora_b"] = jax.random.normal(jax.random.fold_in(key, 3), (r, n)) * 0.02
    x = jax.random.normal(jax.random.fold_in(key, 4), (7, d), jnp.float32)
    return x, xw, ad, acfg


def test_codes_matches_dequant_on_same_codes():
    """Same resident codes, two backends: only kernel numerics differ."""
    x, xw, ad, acfg = _linear_fixture()
    y_codes = substrate.crossbar_linear(x, xw, ad, acfg, backend="codes")
    y_deq = substrate.crossbar_linear(x, xw, ad, acfg, backend="dequant")
    np.testing.assert_allclose(
        np.asarray(y_codes), np.asarray(y_deq), rtol=1e-4, atol=1e-4
    )


def test_codes_backend_no_adapter_is_plain_crossbar():
    x, xw, _, acfg = _linear_fixture()
    y = substrate.crossbar_linear(x, xw, None, acfg, backend="codes")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ rram.dequantize(xw)),
        rtol=1e-4, atol=1e-4,
    )


def test_codes_adc_backend_close_to_codes():
    x, xw, ad, acfg = _linear_fixture(d=256, n=128)
    y_adc = substrate.crossbar_linear(x, xw, ad, acfg, backend="codes_adc")
    y_codes = substrate.crossbar_linear(x, xw, ad, acfg, backend="codes")
    scale = float(jnp.abs(y_codes).max()) + 1e-9
    rel = np.abs(np.asarray(y_adc - y_codes)) / scale
    assert rel.max() < 0.05  # ADC quantization noise, not a different answer


def test_use_backend_options_reach_the_adc():
    """RramConfig plumbing: a coarser ADC (fewer bits) must visibly
    change the codes_adc output — the options are not decorative."""
    x, xw, ad, acfg = _linear_fixture(d=256, n=128)
    with substrate.use_backend("codes_adc", adc_bits=3):
        y_coarse = substrate.crossbar_linear(x, xw, ad, acfg)
    with substrate.use_backend("codes_adc"):
        y_default = substrate.crossbar_linear(x, xw, ad, acfg)
    assert float(jnp.abs(y_coarse - y_default).max()) > 0


def test_linear_dispatches_on_leaf_type():
    """models/layers.linear is the choke point: a CrossbarWeight base leaf
    routes to the substrate, a float leaf keeps the jnp path."""
    from repro.models import layers as L

    x, xw, ad, acfg = _linear_fixture()
    y_sub = L.linear(x, {"w": xw}, ad, acfg, backend="dequant")
    y_ref = dora.adapted_forward(x, rram.dequantize(xw), ad, acfg)
    np.testing.assert_array_equal(np.asarray(y_sub), np.asarray(y_ref))


# -- whole-model parity ------------------------------------------------------


def test_program_model_codes_returns_resident_leaves():
    cfg, params, codes, floats = _programmed_pair("qwen3_1_7b")
    # scan-stacked leaves keep their leading group axis in code space
    leaf = codes["body"][0]["mixer"]["q"]["w"]
    assert isinstance(leaf, rram.CrossbarWeight)
    assert leaf.g_pos.dtype == jnp.uint8 and leaf.g_neg.dtype == jnp.uint8
    assert leaf.g_pos.shape == floats["body"][0]["mixer"]["q"]["w"].shape
    # identical programming event: the float tree is the dequantized codes
    np.testing.assert_allclose(
        np.asarray(rram.dequantize(leaf, dtype=jnp.float32)),
        np.asarray(floats["body"][0]["mixer"]["q"]["w"], np.float32),
        rtol=0.01, atol=1e-4,  # bf16 read-back rounding only
    )


def test_rram_bytes_is_real_measurement_under_codes():
    cfg, params, codes, floats = _programmed_pair("qwen3_1_7b")
    measured = C.rram_bytes(codes)
    # measurement == summed byte size of the actual resident code arrays
    leaves = jax.tree_util.tree_leaves(
        codes, is_leaf=lambda n: isinstance(n, rram.CrossbarWeight)
    )
    expected = sum(
        l.g_pos.nbytes + l.g_neg.nbytes
        for l in leaves
        if isinstance(l, rram.CrossbarWeight)
    )
    assert measured == expected > 0
    # and it coincides with the dequant-mode 2-bytes/weight estimate
    assert measured == C.rram_bytes(floats)


@pytest.mark.parametrize(
    "arch_id,tol",
    [
        ("qwen3_1_7b", 0.05),
        # MoE: the drifted router sits near top-k ties, so the bf16 (float
        # deployment) vs f32 (code read-back) rounding can flip expert
        # choices for a few tokens — parity is looser but still tight
        # relative to the drift the calibration corrects.
        ("deepseek_v2_lite_16b", 0.10),
    ],
)
def test_forward_parity_codes_vs_dequant(arch_id, tol):
    """Dense and MoE (stacked expert codes) forwards agree across
    deployments to programming-quantization/bf16-read-back tolerance."""
    cfg, params, codes, floats = _programmed_pair(arch_id)
    merged_c = C.merge_adapters_for_serve(codes, params["adapters"])
    merged_f = C.merge_adapters_for_serve(floats, params["adapters"])
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, cfg.vocab)
    }
    with substrate.use_backend("codes"):
        lc = T.forward({"base": codes, "adapters": merged_c}, batch, cfg)
    lf = T.forward({"base": floats, "adapters": merged_f}, batch, cfg)
    lc = np.asarray(lc, np.float32)
    lf = np.asarray(lf, np.float32)
    # relative Frobenius error: robust to near-zero logits
    rel = np.linalg.norm(lc - lf) / (np.linalg.norm(lf) + 1e-9)
    assert rel < tol, rel


def test_calibration_step_runs_on_resident_codes():
    """Training over a codes-resident student via the differentiable
    dequant backend: loss finite, adapters update, codes frozen."""
    from repro.core.calibrate import CalibState, jit_calib_step, make_calib_step
    from repro.optim.adam import AdamW, adamw_init

    cfg, params, codes, _ = _programmed_pair("qwen3_1_7b")
    state = CalibState(
        params["base"], codes, params["adapters"],
        adamw_init(params["adapters"]), jnp.zeros((), jnp.int32),
    )
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, cfg.vocab)
    }
    step = make_calib_step(cfg, AdamW(lr=1e-3))
    with substrate.use_backend("dequant"):
        new_state, metrics = jit_calib_step(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).sum()),
        state.adapters, new_state.adapters,
    )
    assert sum(jax.tree_util.tree_leaves(diff)) > 0
    # the array was never rewritten
    np.testing.assert_array_equal(
        np.asarray(new_state.student_base["body"][0]["mixer"]["q"]["w"].g_pos),
        np.asarray(codes["body"][0]["mixer"]["q"]["w"].g_pos),
    )


# -- end-to-end through launch/serve.py --------------------------------------


def test_serve_backend_parity_end_to_end():
    """launch/serve.py --backend codes vs --backend dequant on the same
    drifted deployment: per-step decode logits agree within tolerance."""
    cfg = get_arch("qwen3_1_7b").smoke
    p_codes = serve.load_student(cfg, seed=0, backend="codes")
    p_deq = serve.load_student(cfg, seed=0, backend="dequant")
    assert isinstance(
        p_codes["base"]["body"][0]["mixer"]["q"]["w"], rram.CrossbarWeight
    )
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 6), 0, cfg.vocab)
    with serve.backend_scope("codes"):
        logits_c, _ = serve.prefill_and_cache(p_codes, prompt, cfg, 8)
    with serve.backend_scope("dequant"):
        logits_f, _ = serve.prefill_and_cache(p_deq, prompt, cfg, 8)
    lc = np.asarray(logits_c, np.float32)
    lf = np.asarray(logits_f, np.float32)
    rel = np.linalg.norm(lc - lf) / (np.linalg.norm(lf) + 1e-9)
    assert rel < 0.05, rel
    # the resident-code memory accounting is live on the serve path
    assert C.rram_bytes(p_codes["base"]) == C.rram_bytes(p_deq["base"]) > 0


def test_serve_generate_on_codes_backend():
    cfg = get_arch("qwen3_1_7b").smoke
    params = serve.load_student(cfg, seed=0, backend="codes")
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 4), 0, cfg.vocab)
    with serve.backend_scope("codes"):
        toks, _ = serve.generate(params, prompt, cfg, gen_len=3)
    assert toks.shape == (2, 3)
