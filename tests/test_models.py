"""Per-architecture smoke tests (deliverable f) + decode consistency.

Every assigned arch instantiates its REDUCED config, runs one forward and
one calibration train step on CPU, and asserts output shapes + no NaNs.
Decode-vs-forward consistency is checked on a representative subset of
families (dense/qk_norm, SSM, hybrid, MLA+MoE, SWA).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.core import calibrate as C
from repro.core.calibrate import CalibState, jit_calib_step, make_calib_step
from repro.models import transformer as T
from repro.optim.adam import AdamW, adamw_init

B, S = 2, 16


def _batch(cfg, key=0):
    k = jax.random.PRNGKey(key)
    batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab)}
    if cfg.encoder_layers:
        batch["enc_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 1), (B, S, cfg.d_model), jnp.bfloat16
        )
    if cfg.vision_tokens:
        batch["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 2), (B, cfg.vision_tokens, cfg.d_model),
            jnp.bfloat16,
        )
    return batch


@pytest.fixture(scope="module")
def smoke_runs():
    return {}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_shapes_and_finiteness(arch_id):
    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits = T.forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_calibration_step(arch_id):
    """One full calibration train step: loss finite and adapters update."""
    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    student = C.program_model(params["base"], cfg.rram, jax.random.PRNGKey(1))
    state = CalibState(
        params["base"], student, params["adapters"],
        adamw_init(params["adapters"]), jnp.zeros((), jnp.int32),
    )
    step = make_calib_step(cfg, AdamW(lr=1e-3))
    new_state, metrics = jit_calib_step(step)(state, _batch(cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # adapters changed
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).sum()), state.adapters,
        new_state.adapters,
    )
    assert sum(jax.tree_util.tree_leaves(diff)) > 0


@pytest.mark.parametrize(
    "arch_id",
    ["qwen3_1_7b", "falcon_mamba_7b", "recurrentgemma_9b",
     "deepseek_v2_lite_16b", "mixtral_8x22b"],
)
def test_decode_matches_forward(arch_id):
    """Step-by-step decode logits == full-sequence forward logits (teacher
    weights, no drift) — validates every cache implementation."""
    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    full = T.forward(params, batch, cfg, use_adapters=False)
    cache = T.init_cache(cfg, B, S)
    p = {"base": params["base"], "adapters": T._empty_adapters(params["adapters"])}
    outs = []
    for i in range(S):
        logits, cache = T.decode_step(
            p, cache, batch["tokens"][:, i : i + 1], jnp.int32(i), cfg
        )
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(full, np.float32), rtol=0.15, atol=0.15
    )


def test_decode_matches_forward_encdec():
    cfg = get_arch("seamless_m4t_large_v2").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    full = T.forward(params, batch, cfg, use_adapters=False)
    cache = T.init_cache(cfg, B, S, src_len=S)
    adapters = T._empty_adapters(params["adapters"])
    p = {"base": params["base"], "adapters": adapters}
    cache = T.encode_into_cache(p, cache, batch["enc_embeds"], cfg)
    outs = []
    for i in range(S):
        logits, cache = T.decode_step(
            p, cache, batch["tokens"][:, i : i + 1], jnp.int32(i), cfg
        )
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(full, np.float32), rtol=0.15, atol=0.15
    )


def test_sliding_window_cache_is_rolling():
    """With seq > window, decode must keep working (rolling buffer) and the
    cache allocation stays at the window size."""
    cfg = get_arch("mixtral_8x22b").smoke  # window 16
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    n = cfg.local_window + 8
    cache = T.init_cache(cfg, B, n)
    # body cache is stacked (G, B, L, kvh, hd): L (dim 2) == window
    assert cache["body"][0]["k"].shape[2] == cfg.local_window
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, n), 0, cfg.vocab)
    p = {"base": params["base"], "adapters": T._empty_adapters(params["adapters"])}
    for i in range(n):
        logits, cache = T.decode_step(
            p, cache, toks[:, i : i + 1], jnp.int32(i), cfg
        )
        assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())


def test_count_params_and_adapter_fraction():
    cfg = get_arch("qwen3_1_7b").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    nb, na = T.count_params(params)
    assert nb > 0 and na > 0
    assert na / nb < 0.35  # smoke configs are tiny; fraction is larger than full


def test_calibration_loss_is_layer_local():
    """Gradient w.r.t. layer-l adapters of the summed loss equals the
    gradient of ONLY layer l's MSE — Algorithm 1's locality (DESIGN.md §2)."""
    cfg = get_arch("qwen3_1_7b").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    student = C.program_model(params["base"], cfg.rram, jax.random.PRNGKey(1))
    batch = _batch(cfg)

    def full_loss(ad):
        return T.feature_calibration_loss(
            params["base"], student, ad, batch, cfg
        )[0]

    g = jax.grad(full_loss)(params["adapters"])
    # perturb layer 0's adapter (stacked scan body, leading layer axis):
    # the gradient for layer 1's adapters must be unchanged (no cross-layer
    # gradient flow)
    ad2 = jax.tree_util.tree_map(lambda x: x, params["adapters"])
    la = ad2["body"][0]["mixer"]["q"]["lora_a"]
    ad2["body"][0]["mixer"]["q"]["lora_a"] = la.at[0].add(0.05)
    g2 = jax.grad(full_loss)(ad2)
    a = np.asarray(g["body"][0]["mixer"]["q"]["lora_a"])[1:]
    b = np.asarray(g2["body"][0]["mixer"]["q"]["lora_a"])[1:]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
