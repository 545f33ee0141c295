"""Calibration engine: programming determinism, Algorithm 1 loop, and
end-to-end accuracy recovery on a tiny model (the paper's core claim)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calibrate, dora, rram
from repro.core.calibrate import calibrate_layer, program_model
from repro.core.rram import RramConfig
from repro.optim.adam import AdamW


def test_program_model_deterministic_and_leaf_selective():
    key = jax.random.PRNGKey(0)
    tree = {
        "layer": {"w": jax.random.normal(key, (16, 8))},
        "norm": {"scale": jnp.ones((8,))},
        "ffn": {"gate_w": jax.random.normal(key, (2, 16, 8))},
    }
    cfg = RramConfig(relative_drift=0.2)
    a = program_model(tree, cfg, jax.random.PRNGKey(1))
    b = program_model(tree, cfg, jax.random.PRNGKey(1))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # norms untouched; weights drifted
    np.testing.assert_array_equal(np.asarray(a["norm"]["scale"]), 1.0)
    assert float(jnp.abs(a["layer"]["w"] - tree["layer"]["w"]).max()) > 0
    assert float(jnp.abs(a["ffn"]["gate_w"] - tree["ffn"]["gate_w"]).max()) > 0
    # different programming key -> different deployment state
    c = program_model(tree, cfg, jax.random.PRNGKey(2))
    assert float(jnp.abs(a["layer"]["w"] - c["layer"]["w"]).max()) > 0


def test_rram_bytes_counts_differential_pairs():
    tree = {"layer": {"w": jnp.zeros((16, 8))}, "norm": {"scale": jnp.ones(8)}}
    assert calibrate.rram_bytes(tree) == 2 * 16 * 8


def test_calibrate_layer_restores_single_linear():
    """Algorithm 1 on one layer: drifted W + DoRA trained on 10 samples
    recovers the teacher's outputs."""
    key = jax.random.PRNGKey(0)
    d, k, n = 32, 16, 10
    w_t = jax.random.normal(key, (d, k)) * 0.3
    rcfg = RramConfig(relative_drift=0.20)
    w_r = rram.drifted_weights(w_t, rcfg, jax.random.PRNGKey(1), jnp.float32)
    acfg = dora.AdapterConfig(rank=4, kind="dora")
    adapter = dora.init_adapter(jax.random.PRNGKey(2), d, k, acfg, w_base=w_r)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    y_t = x @ w_t

    def layer_fn(base, ad, xx):
        return dora.adapted_forward(xx, base, ad, acfg)

    before = float(jnp.mean((layer_fn(w_r, adapter, x) - y_t) ** 2))
    adapter, result = calibrate_layer(
        layer_fn, w_r, adapter, x, y_t,
        opt=AdamW(lr=1e-2), max_epochs=500,
    )
    after = float(jnp.mean((layer_fn(w_r, adapter, x) - y_t) ** 2))
    # rank-4 DoRA cannot exactly represent a rank-16 drift restricted to a
    # 10-sample input span; a >5x MSE reduction is the paper-level effect
    assert after < before * 0.2
    assert result.epochs_run == 500  # no threshold -> runs all epochs


def test_calibrate_layer_threshold_early_stop():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (8, 4)) * 0.1
    acfg = dora.AdapterConfig(rank=2)
    ad = dora.init_adapter(jax.random.PRNGKey(1), 8, 4, acfg, w_base=w)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
    y = x @ w  # identical teacher: loss ~0 at init (DoRA init preserving)
    _, res = calibrate_layer(
        lambda b, a, xx: dora.adapted_forward(xx, b, a, acfg),
        w, ad, x, y, max_epochs=50, loss_threshold=1e-6,
    )
    assert res.epochs_run <= 2


def test_dora_beats_lora_on_drifted_linear():
    """Fig. 6's mechanism at unit scale: with drift, DoRA's magnitude
    vector recovers column scales that LoRA at the same rank struggles
    with. We check DoRA reaches a lower MSE than LoRA for equal budget."""
    key = jax.random.PRNGKey(0)
    d, k, n = 48, 32, 10
    w_t = jax.random.normal(key, (d, k)) * 0.3
    rcfg = RramConfig(relative_drift=0.25)
    w_r = rram.drifted_weights(w_t, rcfg, jax.random.PRNGKey(1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    y_t = x @ w_t
    out = {}
    for kind in ("dora", "lora"):
        acfg = dora.AdapterConfig(rank=1, kind=kind)
        ad = dora.init_adapter(jax.random.PRNGKey(2), d, k, acfg, w_base=w_r)
        ad, _ = calibrate_layer(
            lambda b, a, xx: dora.adapted_forward(xx, b, a, acfg),
            w_r, ad, x, y_t, opt=AdamW(lr=5e-3), max_epochs=200,
        )
        out[kind] = float(
            jnp.mean((dora.adapted_forward(x, w_r, ad, acfg) - y_t) ** 2)
        )
    assert out["dora"] < out["lora"]


def test_merge_adapters_for_serve_preserves_outputs():
    """Merged-magnitude serving (Algorithm 2 line 12, §Perf H-6) must be
    numerically identical to the live-norm forward."""
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as T

    cfg = get_arch("qwen3-1.7b").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    student = program_model(params["base"], cfg.rram, jax.random.PRNGKey(1))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)}
    live = T.forward({"base": student, "adapters": params["adapters"]}, batch, cfg)
    merged = calibrate.merge_adapters_for_serve(student, params["adapters"])
    served = T.forward({"base": student, "adapters": merged}, batch, cfg)
    np.testing.assert_allclose(
        np.asarray(live, np.float32), np.asarray(served, np.float32),
        rtol=0.05, atol=0.05,
    )


def test_merge_adapters_handles_moe_stacks():
    import jax
    from repro.configs import get_arch
    from repro.models import transformer as T

    cfg = get_arch("deepseek-v2-lite-16b").smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    merged = calibrate.merge_adapters_for_serve(params["base"], params["adapters"])
    # every dora_m leaf replaced by dora_m_merged
    names = [
        str(getattr(p[-1], "key", ""))
        for p, _ in jax.tree_util.tree_flatten_with_path(merged)[0]
    ]
    assert "dora_m" not in names
    assert any(n == "dora_m_merged" for n in names)


@pytest.mark.parametrize(
    "arch_id",
    # decoder-only / enc-dec untied (lm_head term) / vision prefix
    ["qwen3-1.7b", "seamless_m4t_large_v2", "paligemma_3b"],
)
def test_cached_calib_step_matches_fused_loss(arch_id):
    """§Perf H-9: cached-teacher step loss == fused interleaved loss —
    now for every stack family (the cache stores encoder features, the
    normed enc_out memory, the vision-prefixed decoder chain, and the
    untied lm_head logits)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.deploy.deployment import calibration_batch
    from repro.models import transformer as T
    from repro.optim.adam import adamw_init

    cfg = get_arch(arch_id).smoke
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    student = program_model(params["base"], cfg.rram, jax.random.PRNGKey(1))
    batch = calibration_batch(cfg, 2, 16)
    fused, _ = T.feature_calibration_loss(
        params["base"], student, params["adapters"], batch, cfg
    )
    feats = calibrate.teacher_features(params["base"], batch, cfg)
    state = calibrate.CalibState(
        params["base"], student, params["adapters"],
        adamw_init(params["adapters"]), jnp.zeros((), jnp.int32),
    )
    step = calibrate.make_cached_calib_step(cfg)
    _, metrics = calibrate.jit_calib_step(step)(state, feats, batch)
    # bf16 block outputs re-round under different XLA programs; the
    # per-term structure is identical (enc pairs + dec pairs + lm_head,
    # averaged over n_terms)
    assert abs(float(fused) - float(metrics["loss"])) < 5e-3

    # caching is bitwise-reproducible: a second trace of the same batch
    # is leaf-for-leaf identical, so cache reuse can never drift a run
    feats2 = calibrate.teacher_features(params["base"], batch, cfg)
    for a, b in zip(
        jax.tree_util.tree_leaves(feats), jax.tree_util.tree_leaves(feats2)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
