"""The deployment state of a cell, made on the device from ``--seed``.

One jitted call makes everything, in the types it is served in:

- ``teacher`` (calibration cells only): the float model, bf16, as the
  programming event read it;
- ``w``: the drifted crossbar codes of every projection (uint8 pairs with a
  float32 scale per column, paper eq. 2), the bf16 embedding (normal, with
  the configuration's ``initializer_range`` as its deviation) and the
  float32 norm scales;
- ``a``: the DoRA side-cars. ``"init"`` is the paper's initialisation
  (A random, B zero, M the teacher's column norms), which calibration
  starts from. ``"served"`` gives B a random value so that the side-cars of
  a served model change its output, as calibrated ones do.

Programming maps each column onto the code range by its absolute maximum
and splits signs into the pair; drift multiplies each programmed
conductance by ``1 + N(0, sigma)`` with the log-time sigma
``relative_drift * log1p(hours / tau)`` (paper eq. 1; tau is the
configuration's ``drift_tau_hours``), then rounds back to the code grid.

The layout is the benchmark's own: per-layer leaves stacked over layers
under ``"layers"``. It imports nothing of the program under test.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.reference import LINEARS

SERVED_B_STD = 0.03   # side-car B of a served model: a few % of the output


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def linear_shapes(spec) -> dict:
    """(d_in, d_out) of every per-layer projection."""
    d, nh, nkv, hd, dff = (spec["hidden_size"], spec["num_attention_heads"],
                           spec["num_key_value_heads"], spec["head_dim"],
                           spec["intermediate_size"])
    return {"q": (d, nh * hd), "k": (d, nkv * hd), "v": (d, nkv * hd),
            "o": (nh * hd, d), "gate": (d, dff), "up": (d, dff),
            "down": (dff, d)}


def drift_sigma(spec) -> float:
    dep = spec["deployment"]
    return dep["relative_drift"] * math.log1p(
        dep["drift_hours"] / dep["drift_tau_hours"])


def _program(w, levels):
    """float (.., K, N) -> code pair with a per-column scale."""
    w = w.astype(jnp.float32)
    code_max = levels - 1
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-8)
    scale = absmax / code_max
    c = w / scale
    gp = jnp.clip(jnp.round(jnp.clip(c, 0, code_max)), 0, code_max)
    gn = jnp.clip(jnp.round(jnp.clip(-c, 0, code_max)), 0, code_max)
    return gp, gn, scale


def _drift(g, key, sigma, levels):
    g = g * (1.0 + sigma * jax.random.normal(key, g.shape, jnp.float32))
    return jnp.clip(jnp.round(g), 0, levels - 1).astype(jnp.uint8)


def _linear(key, lead, d_in, d_out, *, spec, sigma, adapters, teacher):
    dep = spec["deployment"]
    levels, rank = dep["conductance_levels"], dep["dora_rank"]
    kw, kp, kn, ka, kb = jax.random.split(key, 5)
    w = (jax.random.normal(kw, lead + (d_in, d_out), jnp.float32)
         * d_in ** -0.5).astype(jnp.bfloat16)
    gp, gn, scale = _program(w, levels)
    codes = {"g_pos": _drift(gp, kp, sigma, levels),
             "g_neg": _drift(gn, kn, sigma, levels), "scale": scale}
    bound = 1.0 / math.sqrt(d_in)
    side = {
        "lora_a": jax.random.uniform(ka, lead + (d_in, rank), jnp.float32,
                                     -bound, bound),
        "lora_b": (jnp.zeros(lead + (rank, d_out), jnp.float32)
                   if adapters == "init" else SERVED_B_STD
                   * jax.random.normal(kb, lead + (rank, d_out), jnp.float32)),
        "dora_m": jnp.linalg.norm(w.astype(jnp.float32), axis=-2),
    }
    return (w if teacher else None), codes, side


def _norm_scale(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("spec_json", "teacher",
                                             "adapters"))
def _make(spec_json, key, teacher, adapters):
    import json

    spec = json.loads(spec_json)
    n = spec["num_hidden_layers"]
    d, hd = spec["hidden_size"], spec["head_dim"]
    sigma = drift_sigma(spec)
    keys = iter(jax.random.split(key, 16))
    layers, sides, teach = {}, {}, {}
    for name, (d_in, d_out) in linear_shapes(spec).items():
        t, c, s = _linear(next(keys), (n,), d_in, d_out, spec=spec,
                          sigma=sigma, adapters=adapters, teacher=teacher)
        layers[name], sides[name], teach[name] = c, s, t
    layers["norm1"] = _norm_scale(next(keys), (n, d))
    layers["norm2"] = _norm_scale(next(keys), (n, d))
    if spec.get("qk_norm"):
        layers["q_norm"] = _norm_scale(next(keys), (n, hd))
        layers["k_norm"] = _norm_scale(next(keys), (n, hd))
    w = {"layers": layers,
         "embed": (spec["initializer_range"] * jax.random.normal(
             next(keys), (spec["vocab_size"], d), jnp.float32
         )).astype(jnp.bfloat16),
         "final_norm": _norm_scale(next(keys), (d,))}
    a = {"layers": sides}
    t_out = None
    if teacher:
        t_out = {"layers": {**{k: teach[k] for k in LINEARS},
                            **{k: v for k, v in layers.items()
                               if k not in LINEARS}}}
    if not spec["tie_word_embeddings"]:
        t, c, s = _linear(next(keys), (), d, spec["vocab_size"], spec=spec,
                          sigma=sigma, adapters=adapters, teacher=teacher)
        w["lm_head"], a["lm_head"] = c, s
        if teacher:
            t_out["lm_head"] = t
    return w, a, t_out


def make(spec, seed: int, *, teacher: bool, adapters: str):
    """``(w, a, teacher)`` for ``spec`` (a configuration file's dict) from
    ``seed``; ``teacher`` is None unless asked for."""
    import json

    if adapters not in ("init", "served"):
        raise ValueError(f"adapters must be 'init' or 'served': {adapters!r}")
    return _make(json.dumps(spec, sort_keys=True), seed_key(seed),
                 bool(teacher), adapters)


def tokens(seed: int, stream: int, shape, vocab: int) -> np.ndarray:
    """Token ids drawn on the host from ``(seed, stream)``."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return rng.integers(0, vocab, size=shape, dtype=np.int64).astype(np.int32)
