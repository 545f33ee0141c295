"""Plain reference of the dense decoder the benchmark's configurations run.

Written from the published layer equations (RMSNorm, rotary attention with
grouped K/V heads and optional per-head q/k RMSNorm, SwiGLU MLP, tied or
untied head) and from the paper's crossbar weights (arXiv 2504.03763, eq. 2
and eq. 6):

    W_r = (G+ - G-) * scale                    differential conductance pair
    y   = (x @ W_r + (x @ A) @ B) * M / ||W_r + A @ B||_col        DoRA

It imports nothing of the program under test and takes only what the
benchmark itself made from the seed (``bench/lib/weights.py``): the
teacher's float weights, the drifted codes and the side-cars. Everything is
computed in float32 at matmul precision "highest", one layer at a time.

``lowp="int8"`` rounds both operands of every matmul to int8 with a scale
per row of the activation and per column of the weight, and is the
lower-precision control that the comparison must fail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LINEARS = ("q", "k", "v", "o", "gate", "up", "down")


def _round_int8(v, axis):
    s = jnp.maximum(jnp.max(jnp.abs(v), axis=axis, keepdims=True), 1e-30)
    return jnp.round(v / s * 127.0) * (s / 127.0)


def mm(x, w, lowp=None):
    """``x @ w`` in float32 at "highest"; ``lowp="int8"`` first rounds x
    per row and w per column to 127 levels."""
    x = x.astype(F32)
    w = w.astype(F32)
    if lowp == "int8":
        x = _round_int8(x, -1)
        w = _round_int8(w, -2)
    elif lowp is not None:
        raise ValueError(f"unknown lower precision {lowp!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def dequant(c):
    """Weight read back from a code pair: (G+ - G-) * scale."""
    return (c["g_pos"].astype(F32) - c["g_neg"].astype(F32)) * c["scale"]


def dora(x, c, a, lowp=None):
    """The DoRA linear over a drifted code pair (training form: the
    column norm is taken of the adapted weight)."""
    w = dequant(c)
    la, lb = a["lora_a"].astype(F32), a["lora_b"].astype(F32)
    y = mm(x, w, lowp) + mm(mm(x, la, lowp), lb, lowp)
    ab = jnp.matmul(la, lb, precision=HIGHEST)
    norm = jnp.sqrt(jnp.maximum(jnp.sum((w + ab) ** 2, axis=0), 1e-6))
    return y * (a["dora_m"].astype(F32) / norm)


def rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotary embedding, half-split convention. x (B, S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


QBLOCK = 512    # queries per attention block: bounds the score tensor


def _attend(q, k, v, q0, hd):
    """Causal attention of the queries at positions q0.. against the keys
    up to the last of them."""
    n = q.shape[1]
    k, v = k[:, :q0 + n], v[:, :q0 + n]
    logits = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST)
    logits = logits / math.sqrt(hd)
    causal = jnp.arange(q0 + n)[None, :] <= (q0 + jnp.arange(n))[:, None]
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v, precision=HIGHEST)


def block(spec, h, lw, la=None, *, teacher=False, lowp=None):
    """One decoder layer on (B, S, d) float32 ``h``. ``lw`` holds this
    layer's weights: float ``w`` leaves for the teacher, code pairs and
    side-cars ``la`` for the student."""
    d, nh, nkv, hd = (spec["hidden_size"], spec["num_attention_heads"],
                      spec["num_key_value_heads"], spec["head_dim"])
    eps = spec["rms_norm_eps"]

    def lin(x, name):
        if teacher:
            return mm(x, lw[name], lowp)
        return dora(x, lw[name], la[name], lowp)

    b, s, _ = h.shape
    x = rms(h, lw["norm1"], eps)
    q = lin(x, "q").reshape(b, s, nh, hd)
    k = lin(x, "k").reshape(b, s, nkv, hd)
    v = lin(x, "v").reshape(b, s, nkv, hd)
    if spec.get("qk_norm"):
        q = rms(q, lw["q_norm"], eps)
        k = rms(k, lw["k_norm"], eps)
    pos = jnp.arange(s)
    q = rope(q, pos, spec["rope_theta"])
    k = rope(k, pos, spec["rope_theta"])
    g = nh // nkv
    # query head j reads K/V head j // g
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    att = jnp.concatenate([_attend(q[:, i:i + QBLOCK], k, v, i, hd)
                           for i in range(0, s, QBLOCK)], axis=1)
    h = h + lin(att.reshape(b, s, nh * hd), "o")
    x = rms(h, lw["norm2"], eps)
    act = jax.nn.silu(lin(x, "gate")) * lin(x, "up")
    return h + lin(act, "down")


@functools.partial(jax.jit, static_argnames=("spec_items", "lowp"))
def _hidden(spec_items, layers, adapters, embed, final_norm, tokens, lowp):
    spec = dict(spec_items)
    h = embed[tokens].astype(F32)

    def body(h, xs):
        lw, la = xs
        return block(spec, h, lw, la, lowp=lowp), None

    h, _ = jax.lax.scan(body, h, (layers, adapters))
    return rms(h, final_norm, spec["rms_norm_eps"])


def _spec_items(spec):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "qk_norm")
    return tuple((k, spec.get(k)) for k in keys)


def served_hidden(spec, w, a, tokens, lowp=None):
    """Final-normed hidden states (B, S, d) of the student over ``tokens``
    (B, S): the drifted codes with their side-cars, layer by layer."""
    return _hidden(_spec_items(spec), w["layers"], a["layers"], w["embed"],
                   w["final_norm"], jnp.asarray(tokens, jnp.int32), lowp)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head_tied(h, embed, lowp):
    return mm(h, embed.T, lowp)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head_untied(h, c, a, lowp):
    return dora(h, c, a, lowp)


def head_logits(spec, w, a, h, lowp=None):
    """Logits (N, V) of final-normed hidden rows ``h`` (N, d)."""
    if spec["tie_word_embeddings"]:
        return _head_tied(h, w["embed"], lowp)
    return _head_untied(h, w["lm_head"], a["lm_head"], lowp)


def served_gaps(spec, w, a, seqs, length, lowp=None, rows=512):
    """For each request ``(prompt, served)`` of ``seqs`` (int arrays), the
    gap by which each served token's reference logit lies below the
    reference's best at that position. With ``lowp``, the gap of the token
    that the lower-precision forward puts first instead. Each request runs
    alone, zero-padded at the end to ``length`` (rounded up to 128), so one
    program serves every request. Returns one float64 array per request."""
    s_max = int(-(-length // 128) * 128)
    out = []
    for p, t in seqs:
        toks = np.zeros((1, s_max), np.int32)
        full = np.concatenate([p, t[:-1]]).astype(np.int32)
        toks[0, :full.size] = full
        hid_ref = served_hidden(spec, w, a, toks)[0]
        hid_low = (None if lowp is None
                   else served_hidden(spec, w, a, toks, lowp)[0])
        lo = len(p) - 1
        idx = np.arange(lo, lo + len(t))
        gaps = []
        for c0 in range(0, idx.size, rows):
            sel = idx[c0:c0 + rows]
            ref = head_logits(spec, w, a, hid_ref[sel])
            if hid_low is None:
                pick = jnp.asarray(np.asarray(t[c0:c0 + rows], np.int32))
            else:
                low = head_logits(spec, w, a, hid_low[sel], lowp)
                pick = jnp.argmax(low, axis=-1)
            chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            gaps.append(np.asarray(jnp.max(ref, axis=-1) - chosen,
                                   np.float64))
        out.append(np.concatenate(gaps))
    return out


# ---------------------------------------------------------------------------
# calibration (Algorithm 1 with cached teacher features, AdamW on the
# side-cars)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("spec_items", "lowp"))
def _teacher_feats(spec_items, teacher, embed, final_norm, tokens, lowp):
    """Inputs of every teacher layer plus the last layer's output,
    (L+1, B, S, d), and the final-normed last output."""
    spec = dict(spec_items)
    h = embed[tokens].astype(F32)

    def body(h, lw):
        o = block(spec, h, lw, teacher=True, lowp=lowp)
        return o, h

    h, ins = jax.lax.scan(body, h, teacher)
    feats = jnp.concatenate([ins, h[None]], axis=0)
    return feats, rms(h, final_norm, spec["rms_norm_eps"])


def _layer_loss(spec, lw, la, fin, fout, lowp):
    out = block(spec, fin, lw, la, lowp=lowp)
    return jnp.mean((fout - out) ** 2)


@functools.partial(jax.jit, static_argnames=("spec_items", "lowp"))
def _loss_and_grads(spec_items, layers, adapters, feats, head, lowp):
    """The feature loss (mean over layer terms, plus the head term for an
    untied head) and its gradient with respect to every side-car."""
    spec = dict(spec_items)
    n_layers = feats.shape[0] - 1

    def body(carry, xs):
        lw, la, fin, fout = xs
        loss, g = jax.value_and_grad(
            lambda la_: _layer_loss(spec, lw, la_, fin, fout, lowp))(la)
        return carry + loss, g

    total, g_layers = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), F32),
        (layers["layers"], adapters["layers"], feats[:-1], feats[1:]))
    grads = {"layers": g_layers}
    n_terms = n_layers
    if head is not None:
        h_in, t_logits = head

        def head_loss(a_):
            out = dora(h_in, layers["lm_head"], a_, lowp)
            return jnp.mean((t_logits - out) ** 2)

        hl, grads["lm_head"] = jax.value_and_grad(head_loss)(
            adapters["lm_head"])
        total = total + hl
        n_terms += 1
    grads = jax.tree_util.tree_map(lambda x: x / n_terms, grads)
    return total / n_terms, grads


def adamw(grads, state, params, opt):
    """One AdamW step with global-norm clipping, as the configuration's
    optimizer states it (``opt``: lr, b1, b2, eps, weight_decay,
    grad_clip)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    if opt["grad_clip"] is not None:
        c = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree_util.tree_map(lambda g: g * c, grads)
    t = state["t"] + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g, state["mu"], grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g, state["nu"],
        grads)
    bc1 = 1 - opt["b1"] ** t
    bc2 = 1 - opt["b2"] ** t

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        return p - opt["lr"] * (u + opt["weight_decay"] * p)

    return (jax.tree_util.tree_map(upd, params, mu, nu),
            {"t": t, "mu": mu, "nu": nu}, grads)


def calibrate(spec, w, a, teacher, tokens, opt, steps, lowp=None):
    """``steps`` calibration steps from side-cars ``a``. Returns the loss
    of each step, the gradient of each step as the optimizer applies it
    (after clipping), and the side-cars after the last step."""
    items = _spec_items(spec)
    tokens = jnp.asarray(tokens, jnp.int32)
    feats, hn = _teacher_feats(items, teacher["layers"], w["embed"],
                               w["final_norm"], tokens, lowp)
    head = None
    if not spec["tie_word_embeddings"]:
        t_logits = mm(hn, teacher["lm_head"], lowp)
        head = (hn, t_logits)
    stud = {"layers": w["layers"]}
    if head is not None:
        stud["lm_head"] = w["lm_head"]
    params = jax.tree_util.tree_map(lambda v: v.astype(F32), a)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = {"t": 0, "mu": zeros, "nu": zeros}
    losses, grads = [], []
    for _ in range(steps):
        loss, g = _loss_and_grads(items, stud, params, feats, head, lowp)
        params, state, g_applied = adamw(g, state, params, opt)
        losses.append(float(loss))
        grads.append(g_applied)
    return losses, grads, params
