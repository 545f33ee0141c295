"""Logical sharding rules: parameter/cache/batch path patterns ->
PartitionSpec, resolved against a concrete mesh.

Axes convention (launch/mesh.py):
  dp axes — ("data",) single-pod, ("pod", "data") multi-pod: batch dim.
  tp axis — "model": attention heads / MLP hidden / expert ff / vocab.

Rules are written for the *trailing* dims of each leaf; leading stacked
dims (scan groups, expert stacks already covered explicitly) are padded
with None. A spec axis is dropped (-> None) when the dim size is not
divisible by the mesh axis size — e.g. batch=1 long_500k cells replicate
the batch dim instead of failing to lower.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

# (regex on leaf path, spec for trailing dims). First match wins.
# "D" -> dp axes, "T" -> tp axis, None -> replicated dim.
PARAM_RULES: Sequence[Tuple[str, Tuple]] = (
    (r"embed/embedding$", ("T", None)),
    (r"lm_head/w$", (None, "T")),
    # attention projections
    (r"mixer/(q|k|v|k_up|v_up)/w$", (None, "T")),
    (r"mixer/kv_down/w$", (None, None)),  # tiny MLA latent projection
    (r"mixer/o/w$", ("T", None)),
    # serve-time fused leaves (substrate/prepared.py concatenates
    # same-input siblings over N): columns stay column-parallel. The
    # _q_kvd fusion drags the tiny kv_down columns along — harmless,
    # column independence makes any contiguous partition exact.
    (r"mixer/(_qkv|_q_kvd|_kup_vup)/w$", (None, "T")),
    (r"ffn(/shared)?/_gate_up/w$", (None, "T")),
    (r"xattn/(q|k|v)/w$", (None, "T")),
    (r"xattn/o/w$", ("T", None)),
    # dense MLP
    (r"ffn/(gate|up)/w$", (None, "T")),
    (r"ffn/down/w$", ("T", None)),
    # MoE expert stacks (E, d, f) / (E, f, d): expert-parallel over the
    # model axis when E divides it (deepseek-v2: 64 experts -> 4/device;
    # the combine is a (tokens, d) psum, §Perf H-2); otherwise fall back
    # to 2D (d over data, ff over model) — mixtral-8x22b's 8x22B experts
    # exceed one HBM at 16-way TP (§Perf H-0).
    (r"ffn/(gate_w|up_w)$", ("EP", "D", "T")),
    (r"ffn/down_w$", ("EP", "T", "D")),
    (r"ffn/router/w$", (None, None)),
    (r"ffn/shared/(gate|up)/w$", (None, "T")),
    (r"ffn/shared/down/w$", ("T", None)),
    # Mamba SSM
    (r"mixer/in_proj/w$", (None, "T")),
    (r"mixer/x_proj/w$", ("T", None)),
    (r"mixer/dt_proj/w$", (None, "T")),
    (r"mixer/out_proj/w$", ("T", None)),
    (r"mixer/a_log$", ("T", None)),
    (r"mixer/(conv_b|d_skip|dt_bias)$", ("T",)),
    (r"mixer/conv_w$", (None, "T")),
    # RG-LRU
    (r"mixer/(in_x|in_y|gate_a|gate_x)/w$", (None, "T")),
    (r"mixer/out/w$", ("T", None)),
    (r"mixer/lambda_p$", ("T",)),
    # norms: EXPLICITLY replicated — stacked-over-layers scale/bias grow
    # past the large-leaf threshold on deep configs, and an explicit rule
    # keeps unmatched_large_leaves() meaning "rules-table gap", not
    # "known-replicated peripheral"
    (r"norm\d*/(scale|bias)$", ()),
    # adapters (lora_a/lora_b/dora_m) + everything else: replicated
)

CACHE_RULES: Sequence[Tuple[str, Tuple]] = (
    # KV cache (B, L, kvh, hd): shard the SEQUENCE dim over the model axis
    # (flash-decoding style): attention reduces over L, so scores shard
    # cleanly and the per-step collectives are the tiny softmax partials,
    # not cache re-gathers (§Perf H-4; head_dim sharding forced XLA into
    # per-step full-cache resharding copies).
    (r"/(k|v)$", ("D", "T", None, None)),
    (r"/c_kv$", ("D", "T", None)),  # MLA latent cache
    (r"/k_rope$", ("D", "T", None)),
    (r"/h$", ("D", "T", None)),  # SSM state (B, d_inner, N)
    (r"/conv$", ("D", None, "T")),
    (r"/enc_out$", ("D", None, None)),
)
# RG-LRU h is (B, d_rnn) — 2D; the ("D","T",None) rule is trimmed to rank.


_AXES = threading.local()


@contextlib.contextmanager
def logical_axes(dp: Tuple[str, ...], tp: str):
    """Bind logical axis names for shard_hint() inside model code."""
    prev = getattr(_AXES, "val", None)
    _AXES.val = {"D": dp, "T": tp}
    try:
        yield
    finally:
        _AXES.val = prev


def shard_hint(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint using logical axis names ('D'/'T'/None);
    no-op when no logical axes are bound (smoke tests, CNN repro)."""
    axes = getattr(_AXES, "val", None)
    if axes is None:
        return x
    resolved = []
    for dim, s in zip(x.shape, spec):
        a = axes.get(s) if isinstance(s, str) else None
        resolved.append(_fit(a, dim))
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def _fit(axis, dim):
    """Drop an axis whose size doesn't divide the dim (needs ambient mesh
    to check; at trace time under jit the mesh is ambient)."""
    if axis is None:
        return None
    mesh = _ambient_mesh()
    if mesh is None:
        return axis
    size = int(np.prod([mesh.shape[a] for a in _as_tuple(axis)]))
    return axis if dim % size == 0 else None


def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _as_tuple(a):
    return a if isinstance(a, tuple) else (a,)


# ---------------------------------------------------------------------------
# tree -> NamedSharding resolution
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_rule(rules, path: str) -> Optional[Tuple]:
    """First rule spec whose pattern matches `path`, else None."""
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return None


def serve_tp_shardable(path: str, rules=PARAM_RULES) -> bool:
    """True when `path` matches a rule that tensor-parallelises ("T"
    anywhere in the spec). Used by the serve-TP wrap policy to decide
    which prepared leaves to column-shard vs leave replicated."""
    spec = match_rule(rules, path)
    return spec is not None and "T" in spec


def resolve_spec(
    path: str,
    shape: Tuple[int, ...],
    axis_sizes,
    rules=PARAM_RULES,
    *,
    dp: Tuple[str, ...] = ("data",),
    tp: str = "model",
) -> P:
    """Resolve a leaf path+shape to a PartitionSpec against a mapping of
    mesh axis name -> size (a live `mesh.shape` works, as does a plain
    dict — no devices required, so the zoo tests run on one device)."""
    spec = match_rule(rules, path)
    if spec is None:
        return P()  # replicated
    if spec and spec[0] == "EP":
        # expert-parallel preferred: shard E over tp; fall back to
        # the 2D (D, T) layout when E doesn't divide the model axis.
        # Stacked scan bodies carry a leading group axis -> 4D.
        e = shape[-3] if len(shape) >= 3 else 0
        if e and e % axis_sizes[tp] == 0:
            spec = ("T", None, None)
        else:
            spec = (None,) + tuple(spec[1:])
    spec = spec[-len(shape):] if len(spec) > len(shape) else spec
    pad = len(shape) - len(spec)
    axes = [None] * pad + [
        (dp if s == "D" else tp if s == "T" else None) for s in spec
    ]
    # divisibility guard per dim
    out = []
    for dim, a in zip(shape, axes):
        if a is None:
            out.append(None)
            continue
        size = int(np.prod([axis_sizes[x] for x in _as_tuple(a)]))
        out.append(a if dim % size == 0 else None)
    return P(*out)


def unmatched_large_leaves(
    abstract_tree: Pytree,
    *,
    min_size: int = 65536,
    rules=PARAM_RULES,
):
    """Leaf paths with >= min_size elements that match no rule — i.e.
    weights that would silently replicate. Adapter/norm leaves are small
    by design; anything big and unmatched is a rules-table gap."""
    bad = []

    def leaf(path, x):
        p = _path_str(path)
        if int(np.prod(x.shape)) >= min_size and match_rule(rules, p) is None:
            bad.append((p, tuple(x.shape)))

    jax.tree_util.tree_map_with_path(leaf, abstract_tree)
    return bad


def tree_shardings(
    abstract_tree: Pytree,
    mesh: Mesh,
    rules=PARAM_RULES,
    *,
    dp: Tuple[str, ...] = ("data",),
    tp: str = "model",
) -> Pytree:
    def leaf(path, x):
        spec = resolve_spec(_path_str(path), x.shape, mesh.shape, rules, dp=dp, tp=tp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, abstract_tree)


def param_shardings(abstract_params: Pytree, mesh: Mesh, *, dp=("data",), tp="model"):
    return tree_shardings(abstract_params, mesh, PARAM_RULES, dp=dp, tp=tp)


def cache_shardings(abstract_cache: Pytree, mesh: Mesh, *, dp=("data",), tp="model"):
    return tree_shardings(abstract_cache, mesh, CACHE_RULES, dp=dp, tp=tp)


def batch_shardings(abstract_batch: Pytree, mesh: Mesh, *, dp=("data",), tp="model"):
    """Inputs: shard leading batch dim over dp (when divisible)."""

    def leaf(path, x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        size = int(np.prod([mesh.shape[a] for a in dp]))
        first = dp if x.shape[0] % size == 0 else None
        return NamedSharding(mesh, P(first, *([None] * (x.ndim - 1))))

    return jax.tree_util.tree_map_with_path(leaf, abstract_batch)


def replicated(tree: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)
