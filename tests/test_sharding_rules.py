"""Sharding-rules coverage over the 10-config model zoo.

``resolve_spec`` takes a plain axis-name -> size mapping, so the whole
zoo is checked abstractly on one device: every large base leaf must
match a rule (silent replication of a big weight is a rules-table gap),
the serve-TP wrap predicate must agree with the rules table, and the
non-divisible drop-to-None behaviour is pinned exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.models import transformer as T
from repro.sharding import rules as R

AXES_16x16 = {"data": 16, "model": 16}


def _abstract_base(cfg):
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    return params["base"]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_zoo_full_config_has_no_unmatched_large_leaves(arch):
    base = _abstract_base(get_arch(arch).full)
    bad = R.unmatched_large_leaves(base)
    assert bad == [], (
        f"{arch}: large base leaves with no sharding rule (would silently "
        f"replicate): {bad}"
    )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_zoo_large_matrices_have_a_sharding_axis(arch):
    """Every big matrix's matched rule must name at least one mesh axis.
    Resolved against size-1 axes so the divisibility guard never fires:
    this checks the rules TABLE (a big matrix mapped to replicated by
    design is a gap), while drop-to-None on awkward dims — e.g. the
    unpadded 256206 seamless vocab vs 16-way TP — stays legal and is
    pinned separately below."""
    base = _abstract_base(get_arch(arch).full)
    axes_1 = {"data": 1, "model": 1}
    replicated_big = []
    # rules that replicate ON PURPOSE (tiny per-layer, big only because
    # they stack over layers): the MLA latent down-projection
    intentional = ("mixer/kv_down/w", "norm")

    def leaf(path, x):
        p = R._path_str(path)
        if int(np.prod(x.shape)) < 1 << 22:  # 4M elements: real matrices
            return
        if any(s in p for s in intentional):
            return
        spec = R.resolve_spec(p, x.shape, axes_1)
        if all(s is None for s in spec):
            replicated_big.append((p, tuple(x.shape), spec))

    jax.tree_util.tree_map_with_path(leaf, base)
    assert replicated_big == [], replicated_big


def test_nondivisible_dim_drops_to_none():
    # 10 % 4 != 0 -> the tp axis drops, the leaf replicates instead of
    # failing to lower; the divisible sibling keeps its spec
    axes = {"data": 2, "model": 4}
    assert R.resolve_spec("mixer/q/w", (16, 10), axes) == P(None, None)
    assert R.resolve_spec("mixer/q/w", (16, 32), axes) == P(None, "model")
    # dp tuple product guards too: ("pod", "data") = 4 does not divide 6
    assert R.resolve_spec(
        "ffn/down/w", (6, 32), {"pod": 2, "data": 2, "model": 4},
        dp=("pod", "data"),
    ) == P(None, None)


def test_expert_stack_prefers_ep_falls_back_2d():
    # 64 experts divide model=16 -> expert-parallel over the model axis
    assert R.resolve_spec(
        "ffn/gate_w", (64, 2048, 1408), AXES_16x16
    ) == P("model", None, None)
    # 8 experts don't divide model=16 -> 2D (d over data, ff over model)
    assert R.resolve_spec(
        "ffn/gate_w", (8, 6144, 16384), AXES_16x16
    ) == P(None, ("data",), "model")


def test_serve_tp_wrap_predicate_matches_rules():
    # column-parallel serve leaves (fused and unfused) are wrappable
    for p in (
        "body/0/mixer/_qkv/w", "body/0/mixer/_q_kvd/w",
        "body/0/mixer/_kup_vup/w", "body/0/ffn/_gate_up/w",
        "body/0/ffn/shared/_gate_up/w", "lm_head/w", "body/0/mixer/o/w",
    ):
        assert R.serve_tp_shardable(p), p
    # explicit-replicate and unmatched paths are not
    for p in (
        "body/0/norm1/scale", "body/0/mixer/kv_down/w",
        "body/0/ffn/router/w", "adapters/whatever/lora_a",
    ):
        assert not R.serve_tp_shardable(p), p


def test_explicit_norm_rule_replicates():
    spec = R.resolve_spec("body/0/norm1/scale", (32, 4096), AXES_16x16)
    assert spec == P(None, None)
    assert R.match_rule(R.PARAM_RULES, "body/0/norm2/bias") == ()


@pytest.mark.parametrize("arch", ["qwen3_1_7b", "deepseek_v2_lite_16b",
                                  "mixtral_8x22b"])
def test_smoke_configs_resolve_without_error(arch):
    """The serve-TP smoke configs: every leaf resolves, and anything the
    wrap policy would shard keeps a 'model' axis at tp=4."""
    base = _abstract_base(get_arch(arch).smoke)
    axes = {"data": 2, "model": 4}

    def leaf(path, x):
        p = R._path_str(path)
        spec = R.resolve_spec(p, x.shape, axes)
        assert len(spec) <= x.ndim

    jax.tree_util.tree_map_with_path(leaf, base)


def test_shard_hint_is_identity_without_logical_axes():
    x = jnp.ones((4, 8))
    assert R.shard_hint(x, "D", "T") is x


def test_shard_hint_raises_without_a_mesh():
    """Logical axes bound with no ambient mesh is a caller error; the
    constraint's failure surfaces instead of returning ``x`` unsharded."""
    with R.logical_axes(("data",), "model"):
        with pytest.raises(RuntimeError, match="mesh"):
            R.shard_hint(jnp.ones((4, 8)), "D", "T")
