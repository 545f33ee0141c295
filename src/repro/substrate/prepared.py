"""Serve-time operand preparation for the codes fast path (ISSUE 6).

``substrate/exec.py::rimc_linear`` historically re-padded every static
operand (codes, per-column scale, LoRA A/B, merged gamma) to tile
multiples on every call — pure per-token overhead at decode shapes, and
one kernel launch per layer leaf on top. This module hoists all of that
to ``Deployment.serve()`` time:

* ``PreparedCrossbar`` — a registered pytree holding the tile-aligned
  codes plus the baked adapter operands (A, B, scale, merged gamma) and,
  optionally, the s8 offset-recode of the codes for the integer MMA
  path. The true (unpadded) ``k``/``n`` extents and the fused segment
  widths ride along as static aux data, so jit caching keys on them.
* ``prepare_crossbar`` / ``fuse_crossbars`` — build one prepared leaf
  from a single ``CrossbarWeight`` + adapter, or from several same-input
  leaves concatenated over N (gate+up, fused QKV, the MLA projection
  pairs). Fusion concatenates codes/scale/gamma over N, concatenates the
  LoRA A factors over r, and block-diagonalizes the B factors — exact
  math, one kernel launch instead of two or three.
* ``prepare_base_for_serve`` — walks a model base tree (with the merged
  adapters) and swaps every servable RRAM leaf for its prepared form,
  fusing where the model structure allows. The deployment's own
  ``codes``/``adapters`` trees are untouched — programming, drift and
  calibration keep the per-leaf layout.
* ``rimc_linear_prepared`` — the hot-path dispatch: per-call tensor work
  is ONLY the activation pad (nothing at all in interpret mode, where
  the autotuner plans unpadded tiles).

Fusion is structure-driven and conservative: only dict siblings that are
2-D/3-D ``{"w": CrossbarWeight}`` leaves with identical leading/K extents
fuse, and cross-attention (``xattn`` subtrees, where q reads the decoder
stream but k/v read the encoder) never fuses q/k/v. MoE expert stacks
(bare stacked ``CrossbarWeight`` values on the einsum path) pass through
unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.rram import CrossbarWeight
from repro.kernels import autotune
from repro.kernels.dora_linear import dora_linear, dora_linear_gemv, recode_s8
from repro.substrate import exec as X


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PreparedCrossbar:
    """Tile-aligned, adapter-baked serving form of one (possibly fused)
    RimcLinear. Arrays may carry leading stack dims (scan groups); the
    kernels consume the 2-D slices ``lax.scan`` produces."""

    g_pos: jax.Array          # (..., Kp, Np) u8, padded codes
    g_neg: jax.Array          # (..., Kp, Np) u8
    scale: jax.Array          # (..., 1, Np) f32 per-column code scale
    lora_a: jax.Array         # (..., Kp, R) f32 (R = sum of fused ranks)
    lora_b: jax.Array         # (..., R, Np) f32 (block-diagonal when fused)
    gamma: jax.Array          # (..., 1, Np) f32 merged DoRA magnitude
    k: int                    # true (unpadded) K
    n: int                    # true (unpadded) N total
    splits: Tuple[int, ...] = ()   # true per-segment N widths when fused
    g_pos_s8: Optional[jax.Array] = None  # offset recode for accum="int8"
    g_neg_s8: Optional[jax.Array] = None

    def tree_flatten(self):
        children = (self.g_pos, self.g_neg, self.scale, self.lora_a,
                    self.lora_b, self.gamma, self.g_pos_s8, self.g_neg_s8)
        return children, (self.k, self.n, self.splits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        gp, gn, scale, a, b, gamma, gp8, gn8 = children
        k, n, splits = aux
        return cls(gp, gn, scale, a, b, gamma, k, n, splits, gp8, gn8)


def _pad2(x: jax.Array, mult_k: int, mult_n: int) -> jax.Array:
    return X._pad_to(X._pad_to(x, mult_k, -2), mult_n, -1)


def serve_alignment(interpret: Optional[bool] = None) -> Tuple[int, int]:
    """(K, N) padding granules for prepared operands: none in interpret
    mode (the tuner plans unpadded tiles), the 128 lane granule on TPU."""
    if interpret is None:
        interpret = X.default_interpret()
    return (1, 1) if interpret else (128, 128)


def _operand_arrays(xw: CrossbarWeight, adapter: Optional[dict], acfg):
    """Unpadded (gp, gn, scale, a, b, gamma) for one leaf, adapters baked."""
    batch = xw.g_pos.shape[:-2]
    k, n = xw.g_pos.shape[-2:]
    adapter = adapter or {}
    if "lora_a" in adapter:
        a = adapter["lora_a"].astype(jnp.float32)
        b = adapter["lora_b"].astype(jnp.float32)
    else:
        a = jnp.zeros(batch + (k, 1), jnp.float32)
        b = jnp.zeros(batch + (1, n), jnp.float32)
    if "dora_m" in adapter:
        raise ValueError(
            "prepare expects merged adapters (merge_adapters_for_serve): "
            "got an unmerged dora_m"
        )
    if acfg.kind == "dora" and "dora_m_merged" in adapter:
        gamma = adapter["dora_m_merged"].astype(jnp.float32)[..., None, :]
    else:
        gamma = jnp.ones(batch + (1, n), jnp.float32)
    # xw.scale is already (..., 1, n) — broadcastable over rows
    scale = xw.scale.astype(jnp.float32)
    return xw.g_pos, xw.g_neg, scale, a, b, gamma


def _finish(gp, gn, scale, a, b, gamma, k, n, splits, align, int8):
    ak, an = align
    gp = _pad2(gp, ak, an)
    gn = _pad2(gn, ak, an)
    return PreparedCrossbar(
        g_pos=gp,
        g_neg=gn,
        scale=X._pad_to(scale, an, -1),
        lora_a=X._pad_to(a, ak, -2),
        lora_b=X._pad_to(b, an, -1),
        gamma=X._pad_to(gamma, an, -1),
        k=k, n=n, splits=splits,
        g_pos_s8=recode_s8(gp) if int8 else None,
        g_neg_s8=recode_s8(gn) if int8 else None,
    )


def prepare_crossbar(
    xw: CrossbarWeight, adapter: Optional[dict], acfg, *,
    align: Optional[Tuple[int, int]] = None, int8: bool = False,
) -> PreparedCrossbar:
    """One leaf -> its prepared serving form (no fusion)."""
    align = serve_alignment() if align is None else align
    gp, gn, scale, a, b, gamma = _operand_arrays(xw, adapter, acfg)
    k, n = xw.g_pos.shape[-2:]
    return _finish(gp, gn, scale, a, b, gamma, k, n, (n,), align, int8)


def fuse_crossbars(
    leaves: Sequence[Tuple[CrossbarWeight, Optional[dict]]], acfg, *,
    align: Optional[Tuple[int, int]] = None, int8: bool = False,
) -> PreparedCrossbar:
    """Fuse same-input leaves into one launch over concatenated N.

    Codes/scale/gamma concatenate along N; the LoRA A factors concatenate
    along r and the B factors become block-diagonal, so
    ``x @ A_cat @ B_blkdiag == concat_i(x @ A_i @ B_i)`` exactly."""
    align = serve_alignment() if align is None else align
    parts = [_operand_arrays(xw, ad, acfg) for xw, ad in leaves]
    k = leaves[0][0].g_pos.shape[-2]
    widths = tuple(xw.g_pos.shape[-1] for xw, _ in leaves)
    ranks = [p[3].shape[-1] for p in parts]
    r_total = sum(ranks)
    gp = jnp.concatenate([p[0] for p in parts], axis=-1)
    gn = jnp.concatenate([p[1] for p in parts], axis=-1)
    scale = jnp.concatenate([p[2] for p in parts], axis=-1)
    gamma = jnp.concatenate([p[5] for p in parts], axis=-1)
    a = jnp.concatenate([p[3] for p in parts], axis=-1)
    b_blocks = []
    off = 0
    for p, r in zip(parts, ranks):
        bi = p[4]
        widths_nd = [(0, 0)] * bi.ndim
        widths_nd[-2] = (off, r_total - off - r)
        b_blocks.append(jnp.pad(bi, widths_nd))
        off += r
    b = jnp.concatenate(b_blocks, axis=-1)
    return _finish(
        gp, gn, scale, a, b, gamma, k, sum(widths), widths, align, int8
    )


def prepared_ref_forward(x: jax.Array, prep: PreparedCrossbar) -> jax.Array:
    """Pure-jnp reference over a prepared leaf (true-extent slices): the
    ``dequant`` backend's view of a prepared tree, and the test oracle."""
    k, n = prep.k, prep.n
    gp = prep.g_pos[..., :k, :n].astype(jnp.float32)
    gn = prep.g_neg[..., :k, :n].astype(jnp.float32)
    w = (gp - gn) * prep.scale[..., :, :n]
    xf = x.astype(jnp.float32)
    y = xf @ w + (xf @ prep.lora_a[..., :k, :]) @ prep.lora_b[..., :, :n]
    return (y * prep.gamma[..., :, :n]).astype(x.dtype)


def rimc_linear_prepared(
    x: jax.Array, prep: PreparedCrossbar, *,
    bm: Optional[int] = None, bn: Optional[int] = None,
    bk: Optional[int] = None, interpret: Optional[bool] = None,
    accum: str = "f32",
) -> jax.Array:
    """Hot-path fused linear over prepared operands: the only per-call
    tensor work besides the kernel is padding x (rows to the M block,
    cols to the prepared K) — a no-op in interpret mode. ``interpret``
    defaults to ``default_interpret()``."""
    if interpret is None:
        interpret = X.default_interpret()
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    m = xf.shape[0]
    kp, npad = prep.g_pos.shape[-2:]
    r = prep.lora_a.shape[-1]
    plan = autotune.select_tiles(
        m, kp, npad, r, interpret=interpret, int8=(accum == "int8")
    )
    bm = plan.bm if bm is None else bm
    bn = plan.bn if bn is None else bn
    bk = plan.bk if bk is None else bk
    xf = X._pad_to(X._pad_to(xf, bm, 0), kp, 1)
    if accum == "int8" and prep.g_pos_s8 is not None:
        gp, gn = prep.g_pos_s8, prep.g_neg_s8
    else:
        gp, gn = prep.g_pos, prep.g_neg
    if xf.shape[0] == bm:
        y = dora_linear_gemv(
            xf, gp, gn, prep.scale, prep.lora_a, prep.lora_b, prep.gamma,
            bn=bn, bk=bk, interpret=interpret, accum=accum,
        )
    else:
        y = dora_linear(
            xf, gp, gn, prep.scale, prep.lora_a, prep.lora_b, prep.gamma,
            bm=bm, bn=bn, bk=bk, interpret=interpret, accum=accum,
        )
    return y[:m, :prep.n].reshape(lead + (prep.n,)).astype(x.dtype)


# ---------------------------------------------------------------------------
# model-tree preparation
# ---------------------------------------------------------------------------

# same-input sibling groups the walker fuses, in precedence order; a key
# consumed by one group is not considered again.
_FUSE_GROUPS = (
    ("_qkv", ("q", "k", "v")),          # self-attention (skipped under xattn)
    ("_q_kvd", ("q", "kv_down")),       # MLA: q + joint KV compression
    ("_kup_vup", ("k_up", "v_up")),     # MLA: latent -> K(nope) + V
    ("_gate_up", ("gate", "up")),       # gated MLP
)


def _servable(node) -> bool:
    """A dict leaf the serving kernels can take over: {"w": codes} with a
    2-D (plain) or 3-D (scan-stacked) code array. 4-D conv codes keep
    their dedicated path."""
    return (
        isinstance(node, dict)
        and isinstance(node.get("w"), CrossbarWeight)
        and node["w"].g_pos.ndim in (2, 3)
    )


def _fusable(b: dict, keys: Tuple[str, ...]) -> bool:
    if not all(_servable(b.get(key)) for key in keys):
        return False
    # identical leading/K extents (same input stream) and code dtypes
    lead_k = {b[key]["w"].g_pos.shape[:-1] for key in keys}
    return len(lead_k) == 1


def prepare_base_for_serve(
    base, adapters, cfg, *, int8: bool = False,
    align: Optional[Tuple[int, int]] = None, faults=None,
):
    """Swap every servable RRAM leaf of ``base`` for its
    ``PreparedCrossbar`` form, fusing same-input sibling leaves. The
    input trees are not mutated; ``adapters`` must be the merged tree
    (``merge_adapters_for_serve``) so gammas bake in exactly.

    ``faults`` (a composed ``FaultMap``) derives the faulty read-back
    view of ``base`` BEFORE any padding/fusion, so the prepared fast
    path serves bitwise the same faulty codes the raw backends read.
    ``Deployment.serve`` pre-applies its map (``self.base`` is already
    the faulty view); the parameter is for direct callers preparing a
    pristine tree."""
    acfg = cfg.adapter
    align = serve_alignment() if align is None else align
    if faults is not None:
        from repro.substrate.exec import faulted_codes

        base = faulted_codes(base, faults, cfg.rram)

    def walk(b, a, cross=False):
        if _servable(b):
            out = dict(b)
            out["w"] = prepare_crossbar(
                b["w"], a if isinstance(a, dict) else None, acfg,
                align=align, int8=int8,
            )
            return out
        if isinstance(b, dict):
            a_d = a if isinstance(a, dict) else {}
            out = {}
            consumed: set = set()
            for fused_key, keys in _FUSE_GROUPS:
                if consumed.intersection(keys):
                    continue
                if fused_key == "_qkv" and (cross or "kv_down" in b):
                    continue
                if _fusable(b, keys):
                    out[fused_key] = {"w": fuse_crossbars(
                        [(b[key]["w"], a_d.get(key)) for key in keys],
                        acfg, align=align, int8=int8,
                    )}
                    consumed.update(keys)
            for key, val in b.items():
                if key in consumed:
                    continue
                out[key] = walk(val, a_d.get(key), cross or key == "xattn")
            return out
        if isinstance(b, list):
            a_l = a if isinstance(a, (list, tuple)) else [None] * len(b)
            return [walk(v, a_l[i], cross) for i, v in enumerate(b)]
        return b

    return walk(base, adapters)


# ---------------------------------------------------------------------------
# tensor-parallel serving (ISSUE 9): column-sharded prepared leaves
# ---------------------------------------------------------------------------

_PREP_FIELDS = (
    "g_pos", "g_neg", "scale", "lora_a", "lora_b", "gamma",
    "g_pos_s8", "g_neg_s8",
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedPrepared:
    """Column-parallel wrapper around one ``PreparedCrossbar``.

    Outside ``shard_map`` the inner arrays are the full global operands,
    placed with ``NamedSharding`` over the last dim; inside the decode
    step's ``shard_map`` body each device sees its ``n_total //
    mesh.shape[axis]`` column slice, which is exactly what the inner
    aux advertises (``local.n`` is the per-shard width). The backend
    runs the ordinary prepared kernel on the local slice and the DoRA
    epilogue finishes with ``tp_column_allgather`` — a zero-scatter +
    ``psum`` over ``axis`` that is bitwise-exact because every output
    column is produced by exactly one shard with the full K reduction.

    Only unpadded leaves whose true N divides the axis size are wrapped
    (see ``shard_prepared_for_serve``); everything else replicates,
    which is bitwise-safe by construction.
    """

    local: PreparedCrossbar   # aux (k, n, splits) describe the PER-SHARD view
    n_total: int
    axis: str = "model"

    def tree_flatten(self):
        return (self.local,), (self.n_total, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def tp_column_allgather(y: jax.Array, n_total: int, axis: str) -> jax.Array:
    """DoRA-epilogue collective: place the local column block of ``y``
    into a zero canvas at this shard's offset and ``psum`` over ``axis``.
    Disjoint blocks -> each output element is one shard's value plus
    exact zeros, so the result matches the unsharded kernel bitwise."""
    n_local = y.shape[-1]
    i = jax.lax.axis_index(axis)
    full = jnp.zeros(y.shape[:-1] + (n_total,), y.dtype)
    start = (0,) * (y.ndim - 1) + (i * n_local,)
    full = jax.lax.dynamic_update_slice(full, y, start)
    return jax.lax.psum(full, axis)


def _prep_like(prep: PreparedCrossbar, fn, aux=None) -> PreparedCrossbar:
    """A PreparedCrossbar whose array children are ``fn(name, child)``
    (None children pass through); aux defaults to ``prep``'s own, so the
    result has the same treedef — required for shard_map spec trees."""
    children, old_aux = prep.tree_flatten()
    new = tuple(
        None if c is None else fn(nm, c)
        for nm, c in zip(_PREP_FIELDS, children)
    )
    return PreparedCrossbar.tree_unflatten(aux or old_aux, new)


def shard_prepared_for_serve(params, mesh, *, tp: str = "model"):
    """Wrap every column-shardable ``PreparedCrossbar`` leaf of a serve
    params tree in ``ShardedPrepared``; return ``(params, stats)``.

    A leaf is shardable when its path matches a tensor-parallel rule in
    ``sharding.rules.PARAM_RULES`` ("T" anywhere in the spec — output-dim
    sharding of a linear is exact regardless of the rule's orientation,
    columns being independent), it carries no N padding (interpret-mode
    alignment), and its true N divides ``mesh.shape[tp]``. MoE expert
    stacks are never prepared leaves and therefore always replicate —
    their combine einsum reduces over E, so sharding E would reorder the
    accumulation and break bitwise parity.
    """
    from repro.sharding import rules as R

    size = int(mesh.shape[tp])
    stats = {"sharded": 0, "replicated": 0}

    def leaf(path, v):
        if not isinstance(v, PreparedCrossbar):
            return v
        p = R._path_str(path)
        ok = (
            size > 1
            and R.serve_tp_shardable(p)
            and v.g_pos.shape[-1] == v.n
            and v.n % size == 0
        )
        if not ok:
            stats["replicated"] += 1
            return v
        stats["sharded"] += 1
        n_local = v.n // size
        local = _prep_like(v, lambda nm, c: c, aux=(v.k, n_local, (n_local,)))
        return ShardedPrepared(local, v.n, tp)

    out = jax.tree_util.tree_map_with_path(
        leaf, params, is_leaf=lambda v: isinstance(v, PreparedCrossbar)
    )
    return out, stats


def serve_param_specs(params):
    """PartitionSpec tree matching ``params``' treedef: ``ShardedPrepared``
    wrappers shard their operands' last dim over their axis (lora_a is
    the K-side factor and replicates); everything else replicates."""

    def leaf(v):
        if isinstance(v, ShardedPrepared):
            def spec(nm, c):
                if nm == "lora_a":
                    return P()
                return P(*([None] * (c.ndim - 1) + [v.axis]))

            return ShardedPrepared(
                _prep_like(v.local, spec), v.n_total, v.axis
            )
        if isinstance(v, PreparedCrossbar):
            return _prep_like(v, lambda nm, c: P())
        return P()

    return jax.tree_util.tree_map(
        leaf, params,
        is_leaf=lambda v: isinstance(v, (ShardedPrepared, PreparedCrossbar)),
    )


def place_serve_params(params, mesh):
    """device_put the serve params tree onto ``mesh`` per
    ``serve_param_specs`` (sharded wrappers' operands land distributed,
    the rest replicated once per device)."""
    specs = serve_param_specs(params)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    return jax.device_put(params, shardings)
