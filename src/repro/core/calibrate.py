"""Calibration engine (paper Algorithm 1 + 2).

Two entry points:

* ``program_model`` — the "deployment" event: every RRAM-resident weight in
  a base pytree is programmed onto the simulated crossbar and drifted
  (deterministic per-leaf keys). Digital peripherals (norms, embeddings,
  conv kernels, SSM A/D, gates' biases, lambda) are left untouched.

* ``CalibrationLoop`` — the layer-wise feature-KD loop for the LM stacks
  (single jitted step over all layers; see
  ``transformer.feature_calibration_loss`` for why that is exactly
  Algorithm 1), with convergence thresholds and epoch caps per the paper.

The CNN reproduction (``core/resnet.py``) uses the literal per-layer loop
(`calibrate_layerwise`) to match the paper's procedure one-to-one.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import rram
from repro.core.rram import RramConfig
from repro.optim.adam import AdamW, adamw_init, adamw_update
from repro.substrate.backends import active_backend_key

Pytree = Any

# Leaf names that live in RRAM (weights that participate in MVMs).
RRAM_LEAF_NAMES = ("w", "gate_w", "up_w", "down_w")


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


def _is_rram_leaf(path) -> bool:
    last = path[-1]
    name = getattr(last, "key", None)
    return name in RRAM_LEAF_NAMES


def program_model(
    base: Pytree,
    cfg: RramConfig,
    key: jax.Array,
    *,
    mode: str = "dequant",
) -> Pytree:
    """Program + drift every RRAM-resident leaf; returns the student base.

    Deterministic: each leaf's drift key is ``fold_in(key, hash(path))`` so
    re-programming with the same key reproduces the same deployment state
    (this is what makes on-restart recovery exact — see runtime/fault.py).

    ``mode`` selects the substrate representation of the returned tree:

    * ``"dequant"`` — drifted weights read back to the leaf's float dtype
      (today's training/CPU fast path).
    * ``"codes"`` — each RRAM leaf becomes a resident ``CrossbarWeight``
      (uint8 ``(G+, G-, scale)``), including the stacked expert /
      scan-group shapes. The SAME programming event: codes are bitwise
      identical across modes for identical keys, so backend parity holds
      to programming-quantization tolerance (the dequant path merely
      rounds the read-back to the float dtype).
    """
    if mode not in ("dequant", "codes"):
        raise ValueError(f"mode must be 'dequant' or 'codes', got {mode!r}")

    def leaf(path, x):
        if not _is_rram_leaf(path):
            return x
        # zlib.crc32 is stable across processes (builtin hash() is salted,
        # which would break exact recovery-on-restart).
        h = jnp.uint32(zlib.crc32(_path_str(path).encode()))
        return program_leaf(x, cfg, jax.random.fold_in(key, h), mode=mode)

    return jax.tree_util.tree_map_with_path(leaf, base)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def program_leaf(
    w: jax.Array, cfg: RramConfig, key: jax.Array, *, mode: str = "codes"
):
    """Program ONE RRAM leaf (its per-leaf key already folded in).

    This is the body ``program_model`` runs per leaf, split out so the
    fleet subsystem can ``jax.vmap`` it over per-chip keys — N chips'
    programming events land as ONE stacked draw, bitwise identical per
    chip to N sequential ``program_model`` calls with the same keys.

    Jitted: run op by op, a stacked (layers, K, N) leaf would hold half a
    dozen f32 temporaries of its full size at once (several GB at
    published widths); compiled, the chain fuses into its outputs.
    """
    if w.ndim == 2:
        if mode == "codes":
            return rram.programmed_codes(w, cfg, key)
        return rram.drifted_weights(w, cfg, key, dtype=w.dtype)
    # stacked weights: (E, d, k) experts or (G, ..., d, k) scan bodies —
    # program each matrix; drift is i.i.d. so one vmapped call suffices.
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    keys = jax.random.split(key, flat.shape[0])
    if mode == "codes":
        out = jax.vmap(lambda m, kk: rram.programmed_codes(m, cfg, kk))(
            flat, keys
        )
        return rram.CrossbarWeight(
            g_pos=out.g_pos.reshape(lead + w.shape[-2:]),
            g_neg=out.g_neg.reshape(lead + w.shape[-2:]),
            scale=out.scale.reshape(lead + (1, w.shape[-1])),
        )
    out = jax.vmap(
        lambda m, kk: rram.drifted_weights(m, cfg, kk, dtype=w.dtype)
    )(flat, keys)
    return out.reshape(lead + w.shape[-2:])


def drift_model(
    base: Pytree,
    cfg: RramConfig,
    key: jax.Array,
    *,
    hours: Optional[float] = None,
    event_index,
    clock_offset: float = 0.0,
    sigma=None,
) -> Pytree:
    """One drift-clock tick over a codes-resident model: re-drift every
    resident ``CrossbarWeight`` WITHOUT reprogramming (the array is never
    rewritten; time simply passes and the conductances relax further).
    ``clock_offset`` is the field time already elapsed before this tick —
    the tick draws the variance INCREMENT over ``[offset, offset+hours]``
    (``rram.drift_sigma_increment``), so slicing the same timeline into
    different ticks accumulates the same total drift.

    Deterministic and replayable: each leaf's event key is
    ``fold_in(fold_in(key, crc32(path)), event_index)``, so a deployment
    that knows its programming key and the ordered list of elapsed-hour
    events can reproduce the exact post-drift codes from scratch
    (``deploy.Deployment.restore`` relies on this).

    Fleet form: ``sigma`` (overriding ``hours``) and ``event_index`` may
    be traced scalars, so ``jax.vmap`` over per-chip ``(codes, key,
    sigma, event_index)`` re-drifts a whole fleet in one dispatch
    (``fleet.Fleet.advance``).
    """
    if (hours is None) == (sigma is None):
        raise ValueError("drift_model needs exactly one of hours= or sigma=")
    if sigma is None:
        sigma = rram.drift_sigma_increment(cfg, clock_offset, hours)
    n_drifted = 0

    def leaf(path, x):
        nonlocal n_drifted
        if not isinstance(x, rram.CrossbarWeight):
            return x
        n_drifted += 1
        if isinstance(sigma, (int, float)) and sigma <= 0.0:
            return x
        h = jnp.uint32(zlib.crc32(_path_str(path).encode()))
        k = jax.random.fold_in(key, h)
        return _drift_leaf(x, cfg, k, sigma, event_index)

    out = jax.tree_util.tree_map_with_path(
        leaf, base, is_leaf=lambda n: isinstance(n, rram.CrossbarWeight)
    )
    if n_drifted == 0:
        raise ValueError(
            "drift_model needs a codes-resident tree (CrossbarWeight leaves); "
            "got a float tree — program with mode='codes' first"
        )
    return out


@functools.partial(jax.jit, static_argnames=("cfg",))
def _drift_leaf(xw, cfg, key, sigma, event_index):
    """One leaf's drift tick, compiled for the same reason as
    ``program_leaf``: op by op it would hold several full-size f32
    temporaries of a stacked leaf at once."""
    return rram.apply_drift(xw, cfg, key, sigma=sigma, event_index=event_index)


def rram_bytes(base: Pytree) -> int:
    """Bytes of weights resident in RRAM.

    For a codes-mode tree this is a real MEASUREMENT: the summed byte
    size of the uint8 code arrays actually resident in device memory.
    For a dequant-mode (float) tree it remains the 2-bytes-per-weight
    estimate of what the array WOULD hold (differential uint8 pairs).
    """
    total = 0

    def leaf(path, x):
        nonlocal total
        if isinstance(x, rram.CrossbarWeight):
            total += int(x.g_pos.size) + int(x.g_neg.size)
        elif _is_rram_leaf(path):
            total += 2 * int(x.size)  # G+ and G- codes, 1 byte each
        return x

    jax.tree_util.tree_map_with_path(
        leaf, base, is_leaf=lambda n: isinstance(n, rram.CrossbarWeight)
    )
    return total


def sram_bytes(adapters: Pytree) -> int:
    """Bytes of calibration state resident in SRAM: the DoRA/LoRA side-car
    arrays at their actual storage width. This is the digital memory the
    paper trades against RRAM rewrites — compare with ``rram_bytes`` on
    the same deployment (serve/train print both at startup).
    """
    total = 0
    for x in jax.tree_util.tree_leaves(adapters):
        if hasattr(x, "nbytes"):
            total += int(x.nbytes)
    return total


def calibrated_fraction(base: Pytree, adapters: Pytree) -> float:
    """Fraction of model parameters that calibration trains (paper's 2.34%
    headline): adapter params / base params, counting a codes-resident
    ``CrossbarWeight`` as its logical weight count."""
    from repro.models.transformer import count_params

    n_base, n_adapters = count_params({"base": base, "adapters": adapters})
    return n_adapters / max(n_base, 1)


def merge_adapters_for_serve(base: Pytree, adapters: Pytree) -> Pytree:
    """Algorithm 2 line 12 over a whole model: replace every DoRA
    ``dora_m`` with ``dora_m_merged = M / ||W_r + A@B||_col`` so serving
    never recomputes weight-sized norms (§Perf H-6).

    Walks base/adapters jointly; adapter dicts are recognized by their
    ``lora_a`` leaf, and the paired base weight is the sibling RRAM leaf.
    """
    from repro.core import dora as dora_lib
    from repro.models.moe import _stacked_column_norm

    def walk(b, a):
        if isinstance(a, dict) and "lora_a" in a:
            if "dora_m" not in a:
                return a  # LoRA: nothing to merge
            w = b["w"] if isinstance(b, dict) and "w" in b else b
            if isinstance(w, rram.CrossbarWeight):
                # codes-resident base: the norm is a one-off digital
                # read-back at deployment; the resulting gamma is exactly
                # what the fused kernel's epilogue consumes.
                w = rram.dequantize(w)
            m = a["dora_m"].astype(jnp.float32)
            # disambiguate by lora_b rank: (r,k) plain/conv; (E,r,k)
            # stacked (experts OR scan groups — same math); (G,E,r,k)
            # scan-stacked expert stacks.
            lb = a["lora_b"]
            if lb.ndim == 2 and w.ndim == 4:  # conv (kh,kw,cin,cout)
                norm = dora_lib.conv_column_norm(w, a["lora_a"], lb)
            elif lb.ndim == 2:
                norm = dora_lib.column_norm(w, a["lora_a"], lb)
            elif lb.ndim == 3:
                norm = _stacked_column_norm(w, a["lora_a"], lb)
            else:
                norm = jax.vmap(_stacked_column_norm)(w, a["lora_a"], lb)
            out = {k: v for k, v in a.items() if k != "dora_m"}
            out["dora_m_merged"] = m / norm
            return out
        if isinstance(a, dict):
            return {
                k: walk(b[k] if isinstance(b, dict) and k in b else b, v)
                for k, v in a.items()
            }
        if isinstance(a, list):
            return [walk(b[i], v) for i, v in enumerate(a)]
        return a

    return walk(base, adapters)


# ---------------------------------------------------------------------------
# Literal per-layer calibration loop (Algorithm 1) — used by the CNN repro
# and exposed for any model that provides per-layer (forward, params).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LayerCalibResult:
    losses: list
    epochs_run: int


def calibrate_layer(
    layer_fn: Callable[[Pytree, Pytree, jax.Array], jax.Array],
    student_layer_base: Pytree,
    adapter: Pytree,
    teacher_in: jax.Array,
    teacher_out: jax.Array,
    *,
    opt: AdamW = AdamW(lr=1e-3),
    max_epochs: int = 20,
    loss_threshold: float = 0.0,
    batch_size: Optional[int] = None,
) -> Tuple[Pytree, LayerCalibResult]:
    """Algorithm 1 lines 5-10 for a single layer.

    ``layer_fn(base, adapter, x) -> y``. ``teacher_in/out`` are the cached
    clean features for the calibration samples (N leading dim).
    Runs ``max_epochs`` epochs of full-batch Adam (paper uses batch 1 over
    10 samples; full-batch over <=10 samples is the same data regime and
    jit-friendlier — ``batch_size`` restores per-sample updates if set).
    """
    opt_state = adamw_init(adapter)

    def loss_fn(ad, x, y):
        pred = layer_fn(student_layer_base, ad, x)
        d = pred.astype(jnp.float32) - y.astype(jnp.float32)
        return jnp.mean(d * d)

    @jax.jit
    def step(ad, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(ad, x, y)
        ad, opt_state = adamw_update(grads, opt_state, ad, opt)
        return ad, opt_state, loss

    n = teacher_in.shape[0]
    bs = batch_size or n
    losses = []
    epochs_run = 0
    for epoch in range(max_epochs):
        epoch_loss = 0.0
        for i in range(0, n, bs):
            adapter, opt_state, loss = step(
                adapter, opt_state, teacher_in[i : i + bs], teacher_out[i : i + bs]
            )
            epoch_loss += float(loss) * min(bs, n - i)
        epoch_loss /= n
        losses.append(epoch_loss)
        epochs_run = epoch + 1
        if epoch_loss <= loss_threshold:
            break
    return adapter, LayerCalibResult(losses=losses, epochs_run=epochs_run)


# ---------------------------------------------------------------------------
# Whole-model jitted calibration state (LM stacks) — built by launch/train.py
# ---------------------------------------------------------------------------


class CalibState:
    """Plain pytree container: (teacher_base, student_base, adapters,
    opt_state, step). Registered as a pytree for jit/pjit."""

    def __init__(self, teacher_base, student_base, adapters, opt_state, step):
        self.teacher_base = teacher_base
        self.student_base = student_base
        self.adapters = adapters
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (
            (self.teacher_base, self.student_base, self.adapters,
             self.opt_state, self.step),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    CalibState, CalibState.tree_flatten, CalibState.tree_unflatten
)


def teacher_features(teacher_base, batch, cfg):
    """Algorithm 1 line 3: run the frozen teacher ONCE over the calibration
    batch and cache every block's input/output. With ~10 calibration
    samples the same features serve every epoch — the per-step teacher
    recompute (≈⅓ of step FLOPs and bytes) is amortized away (§Perf H-9).

    Returns a dict of cached teacher activations:

    - ``"dec"`` — (Ld+1, B, S_tot, d): decoder block inputs plus the final
      block output. S_tot includes the vision prefix for VLM configs.
    - ``"enc"`` — (Le+1, B, S_src, d) for enc-dec configs: encoder block
      inputs plus the final (pre-norm) encoder output.
    - ``"enc_out"`` — (B, S_src, d): normed encoder output, the cross-
      attention memory every decoder block (teacher AND student) consumes.
    - ``"head_in"`` / ``"head_out"`` for untied heads: final-norm output
      and the teacher logits it produces (the lm_head lives in RRAM, so
      its side-car is calibrated against cached logits too).
    """
    from repro.models import transformer as T
    import jax.numpy as jnp

    base = teacher_base
    h = T.L.embed(batch["tokens"], base["embed"],
                  scale_by_sqrt_dim=cfg.embed_scale)
    mask = None
    if cfg.vision_tokens and "patch_embeds" in batch:
        h = jnp.concatenate([batch["patch_embeds"].astype(h.dtype), h], axis=1)
        mask = T._prefix_mask(h.shape[1], batch["patch_embeds"].shape[1])
    s = h.shape[1]
    positions = jnp.arange(s)[None]
    kinds = cfg.layer_kinds()
    pro, n_groups, epi = cfg.body_layout()
    p = cfg.scan_period
    out = {}

    enc_out = None
    if cfg.encoder_layers:
        src = batch["enc_embeds"].astype(h.dtype)
        s_src = src.shape[1]
        enc_mask = jnp.ones((s_src, s_src), bool)
        enc_pos = jnp.arange(s_src)[None]

        def enc_run(he, tb):
            return T.block_forward(he, tb, {}, cfg, "attn", "mlp",
                                   positions=enc_pos, mask=enc_mask)

        if cfg.unroll:
            enc_feats = [src]
            he = src
            for tb in base["encoder"]:
                he = enc_run(he, tb)
                enc_feats.append(he)
            enc_feats = jnp.stack(enc_feats)
        else:
            def enc_step(he, tb):
                o = enc_run(he, tb)
                return o, o

            he, ys = jax.lax.scan(enc_step, src, base["encoder"])
            enc_feats = jnp.concatenate([src[None], ys], axis=0)
        enc_out = T._norm(he, base["enc_norm"], cfg)
        out["enc"] = enc_feats  # (Le+1, B, S_src, d)
        out["enc_out"] = enc_out

    feats = [h]

    def run(h, b, kind):
        mixer, ffn = kind
        return T.block_forward(h, b, {}, cfg, mixer, ffn,
                               positions=positions, mask=mask, enc_out=enc_out)

    for i in range(pro):
        h = run(h, base["prologue"][i], kinds[i])
        feats.append(h)
    if n_groups:
        body_kinds = [kinds[pro + j] for j in range(p)]

        def group(h, bs):
            outs = []
            for j in range(p):
                h = run(h, bs[j], body_kinds[j])
                outs.append(h)
            return h, jnp.stack(outs)

        h, ys = jax.lax.scan(group, h, base["body"])  # ys: (G, p, B, S, d)
        feats.extend(list(ys.reshape((-1,) + ys.shape[2:])))
    for j, i in enumerate(range(cfg.n_layers - epi, cfg.n_layers)):
        h = run(h, base["epilogue"][j], kinds[i])
        feats.append(h)
    out["dec"] = jnp.stack(feats)  # (Ld+1, B, S_tot, d)

    if not cfg.tie_lm_head:
        hn = T._norm(h, base["final_norm"], cfg)
        out["head_in"] = hn
        out["head_out"] = T.L.linear(hn, base["lm_head"], {}, cfg.adapter)
    return out


def make_cached_calib_loss(cfg):
    """The cached-teacher calibration loss as a standalone function
    ``loss_fn(adapters, student_base, feats, batch)``: each student
    block sees feats["dec"][l] (/ feats["enc"][l]) and matches the
    cached teacher output at l+1. Mirrors ``feature_calibration_loss``
    term-for-term — encoder pairs, decoder pairs, and the untied
    lm_head logits term, averaged over ``n_terms`` — so cached and
    fused calibration follow the same trajectory. Shared by the
    single-chip/vmapped step below and the mesh-parallel fleet path
    (which needs raw per-chip gradients for the compressed cross-device
    all-reduce)."""
    from repro.models import transformer as T
    import jax.numpy as jnp

    kinds = cfg.layer_kinds()
    pro, n_groups, epi = cfg.body_layout()
    p = cfg.scan_period

    def loss_fn(adapters, sbase, feats, batch):
        dec = feats["dec"]
        s = dec.shape[2]
        positions = jnp.arange(s)[None]
        mask = None
        if cfg.vision_tokens and "patch_embeds" in batch:
            mask = T._prefix_mask(s, batch["patch_embeds"].shape[1])
        loss = jnp.zeros((), jnp.float32)
        n_terms = 0
        enc_out = feats.get("enc_out")

        if cfg.encoder_layers:
            enc = feats["enc"]
            s_src = enc.shape[2]
            enc_mask = jnp.ones((s_src, s_src), bool)
            enc_pos = jnp.arange(s_src)[None]

            if cfg.unroll:
                for l, (sb, a_) in enumerate(
                    zip(sbase["encoder"], adapters.get("encoder"))
                ):
                    s_out = T.block_forward(
                        enc[l], sb, a_, cfg, "attn", "mlp",
                        positions=enc_pos, mask=enc_mask,
                    )
                    loss = loss + T._mse(enc[l + 1], s_out)
            else:
                def enc_pair(carry, xs):
                    acc, idx = carry
                    sb, a_ = xs
                    fin = jax.lax.dynamic_index_in_dim(
                        enc, idx, keepdims=False
                    )
                    fout = jax.lax.dynamic_index_in_dim(
                        enc, idx + 1, keepdims=False
                    )
                    s_out = T.block_forward(
                        fin, sb, a_, cfg, "attn", "mlp",
                        positions=enc_pos, mask=enc_mask,
                    )
                    return (acc + T._mse(fout, s_out), idx + 1), None

                (loss, _), _ = jax.lax.scan(
                    T._maybe_remat(enc_pair, cfg), (loss, 0),
                    (sbase["encoder"], adapters.get("encoder")),
                )
            n_terms += cfg.encoder_layers

        def pair(l, b, a_, kind):
            mixer, ffn = kind
            s_out = T.block_forward(
                dec[l], b, a_, cfg, mixer, ffn, positions=positions,
                mask=mask, enc_out=enc_out,
            )
            return T._mse(dec[l + 1], s_out)

        for i in range(pro):
            loss += pair(i, sbase["prologue"][i], adapters["prologue"][i],
                         kinds[i])
            n_terms += 1
        if n_groups:
            body_kinds = [kinds[pro + j] for j in range(p)]
            body_feats = dec[pro:pro + n_groups * p + 1]

            def group(carry, xs):
                acc, idx = carry
                bs, as_ = xs
                for j in range(p):
                    mixer, ffn = body_kinds[j]
                    fin = jax.lax.dynamic_index_in_dim(
                        body_feats, idx * p + j, keepdims=False
                    )
                    fout = jax.lax.dynamic_index_in_dim(
                        body_feats, idx * p + j + 1, keepdims=False
                    )
                    s_out = T.block_forward(
                        fin, bs[j], as_[j], cfg, mixer, ffn,
                        positions=positions, mask=mask, enc_out=enc_out,
                    )
                    acc = acc + T._mse(fout, s_out)
                return (acc, idx + 1), None

            # remat as the fused loss does: without it the backward pass
            # keeps every layer's dequantized f32 weights alive at once
            (loss, _), _ = jax.lax.scan(
                T._maybe_remat(group, cfg), (loss, 0),
                (sbase["body"], adapters.get("body")),
            )
            n_terms += n_groups * p
        for j, i in enumerate(range(cfg.n_layers - epi, cfg.n_layers)):
            loss += pair(
                pro + n_groups * p + j, sbase["epilogue"][j],
                adapters["epilogue"][j], kinds[i],
            )
            n_terms += 1

        if not cfg.tie_lm_head:
            s_logits = T.L.linear(
                feats["head_in"], sbase["lm_head"],
                adapters.get("lm_head"), cfg.adapter,
            )
            loss = loss + T._mse(feats["head_out"], s_logits)
            n_terms += 1
        return loss / n_terms

    return loss_fn


def make_cached_calib_step(cfg, opt: AdamW = AdamW(lr=1e-3)):
    """Calibration step against cached teacher features: each student
    block sees feats[l] and matches feats[l+1]. Teacher forward cost: 0."""
    loss_fn = make_cached_calib_loss(cfg)

    def step(state: CalibState, feats, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.adapters, state.student_base, feats, batch
        )
        adapters, opt_state = adamw_update(
            grads, state.opt_state, state.adapters, opt
        )
        new_state = CalibState(
            state.teacher_base, state.student_base, adapters, opt_state,
            state.step + 1,
        )
        return new_state, {"loss": loss}

    return step


def calib_step_program(step):
    """The program every caller compiles a calibration step as: the
    frozen teacher and student bases enter it but never leave it.

    ``program(teacher_base, student_base, (adapters, opt_state, step),
    *args)`` returns ``((adapters, opt_state, step), *rest)`` for a
    ``step(state, *args) -> (CalibState, *rest)``. A compiled step that
    returned the whole ``CalibState`` would write a fresh copy of both
    bases (several GB at full width) on every step. Jitted, it is the
    module ``jit_calib_step``."""

    def calib_step(teacher_base, student_base, trainable, *args):
        new, *rest = step(CalibState(teacher_base, student_base, *trainable),
                          *args)
        return ((new.adapters, new.opt_state, new.step), *rest)

    return calib_step


# ---------------------------------------------------------------------------
# compiled-program registry (mirrors deploy/serving.py and fleet/fleet.py):
# ONE jitted calibration step and ONE jitted teacher forward per (kind,
# cfg, opt, trace backend), so repeated ``Deployment.calibrate`` calls
# reuse their programs instead of tracing, lowering and loading them
# again. The backend is read at trace time, so the key takes its identity
# WITH its options (``active_backend_key``). Batch shapes (10 x 32
# against 320 x 32) are handled by jax.jit's argument cache on the SAME
# callable.
# ---------------------------------------------------------------------------

_CALIB_PROGRAMS: Dict[Tuple, Any] = {}


def _registry_get(kind: str, cfg, opt, build):
    key = (kind, cfg, opt, active_backend_key())
    fn = _CALIB_PROGRAMS.get(key)
    if fn is None:
        fn = _CALIB_PROGRAMS[key] = build()
    return fn


def calib_compile_count(cfg) -> int:
    """Total compiled-computation count across this cfg's registered
    calibration programs (steps and teacher forwards, any opt, any
    backend). Flat across repeated same-shape ``Deployment.calibrate``
    calls; a new batch shape adds one compile to each program it runs."""
    return sum(fn._cache_size() for key, fn in _CALIB_PROGRAMS.items()
               if key[1] == cfg)


def jit_calib_step(step, *, wrap=None, key=None):
    """``jax.jit`` of ``calib_step_program(step)`` behind the
    ``step(state, *args)`` signature. ``wrap`` transforms the program
    before it is jitted (the fleet vmaps it over chips or shard_maps it
    over a mesh).

    ``key`` is the ``(kind, cfg, opt)`` that ``step`` was made from
    (``"cached"``: ``make_cached_calib_step``, ``"fused"``:
    ``make_calib_step``). With it the jitted step is kept in the
    calibration registry under that key and the active backend: the first
    request builds it from ``step``, later ones return the same callable
    and leave theirs unused."""
    if key is not None:
        return _registry_get(*key, lambda: jit_calib_step(step, wrap=wrap))
    program = calib_step_program(step)
    jitted = jax.jit(program if wrap is None else wrap(program))

    def run(state: CalibState, *args):
        trainable, *rest = jitted(
            state.teacher_base, state.student_base,
            (state.adapters, state.opt_state, state.step), *args,
        )
        return (CalibState(state.teacher_base, state.student_base,
                           *trainable), *rest)

    run._cache_size = jitted._cache_size   # compile count, as on a jit
    return run


def teacher_features_program(cfg):
    """``teacher_features`` for ``cfg`` as one jitted program
    ``(teacher_base, batch) -> feats``, kept in the calibration registry
    under the active backend: the teacher forward is compiled once, not
    an eager embed plus a freshly lowered scan on every call. The
    features themselves are computed by every call of the program."""

    def teacher_forward(teacher_base, batch):  # jit_teacher_forward
        return teacher_features(teacher_base, batch, cfg)

    return _registry_get("teacher", cfg, None,
                         lambda: jax.jit(teacher_forward))


def make_calib_step(
    cfg,
    opt: AdamW = AdamW(lr=1e-3),
):
    """Build the jittable whole-model calibration step for an LM config."""
    from repro.models import transformer as T

    def calib_step(state: CalibState, batch: Dict) -> Tuple[CalibState, Dict]:
        def loss_fn(adapters):
            return T.feature_calibration_loss(
                state.teacher_base, state.student_base, adapters, batch, cfg
            )

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.adapters
        )
        adapters, opt_state = adamw_update(
            grads, state.opt_state, state.adapters, opt
        )
        new_state = CalibState(
            state.teacher_base,
            state.student_base,
            adapters,
            opt_state,
            state.step + 1,
        )
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_state, metrics

    return calib_step
