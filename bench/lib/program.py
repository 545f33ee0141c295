"""The one place the benchmark talks to the program under test's types:
its model configuration, and its parameter trees, built from the
benchmark's own arrays (``bench/lib/weights.py``)."""
from __future__ import annotations

import jax

from bench.lib.reference import LINEARS

_ATTN = ("q", "k", "v", "o")
_FFN = ("gate", "up", "down")


def model_config(spec):
    """The program's ``ModelConfig`` for a configuration file's dict. Every
    size comes from the file; what the program cannot run is refused."""
    from repro.core.dora import AdapterConfig
    from repro.core.rram import RramConfig
    from repro.models.attention import AttentionConfig
    from repro.models.layers import MlpConfig
    from repro.models.transformer import ModelConfig

    if spec.get("rope_scaling"):
        raise ValueError("the program has no RoPE scaling")
    if spec["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    if spec["hidden_act"] != "silu" or spec.get("attention_bias"):
        raise ValueError("the program runs a SwiGLU MLP without biases")
    d = spec["hidden_size"]
    dep = spec["deployment"]
    cfg = ModelConfig(
        name=spec["name"], d_model=d, n_layers=spec["num_hidden_layers"],
        vocab=spec["vocab_size"],
        attn=AttentionConfig(
            d_model=d, num_heads=spec["num_attention_heads"],
            num_kv_heads=spec["num_key_value_heads"],
            head_dim=spec["head_dim"], rope_theta=float(spec["rope_theta"]),
            qk_norm=bool(spec.get("qk_norm")),
        ),
        mlp=MlpConfig(d_model=d, d_ff=spec["intermediate_size"], gated=True,
                      activation="silu"),
        norm="rms", tie_lm_head=bool(spec["tie_word_embeddings"]),
        adapter=AdapterConfig(rank=dep["dora_rank"], kind="dora"),
        rram=RramConfig(levels=dep["conductance_levels"],
                        relative_drift=dep["relative_drift"]),
    )
    if cfg.body_layout() != (0, cfg.n_layers, 0):
        raise ValueError(f"unexpected layer layout {cfg.body_layout()}")
    return cfg


def _crossbar(c):
    from repro.core.rram import CrossbarWeight

    return CrossbarWeight(g_pos=c["g_pos"], g_neg=c["g_neg"],
                          scale=c["scale"])


def _layer_base(layers, leaf):
    mixer = {n: {"w": leaf(layers[n])} for n in _ATTN}
    for n in ("q_norm", "k_norm"):
        if n in layers:
            mixer[n] = {"scale": layers[n]}
    return {"norm1": {"scale": layers["norm1"]}, "mixer": mixer,
            "norm2": {"scale": layers["norm2"]},
            "ffn": {n: {"w": leaf(layers[n])} for n in _FFN}}


def _base(w, leaf, head=None):
    base = {"embed": {"embedding": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "prologue": [], "body": [_layer_base(w["layers"], leaf)],
            "epilogue": []}
    if head is not None:
        base["lm_head"] = {"w": leaf(head)}
    return base


def to_program(cfg, w, a, teacher=None):
    """``(codes_base, adapters, teacher_base)`` as the program's
    ``Deployment`` holds them, checked against the tree the program's own
    initialisation builds for ``cfg``."""
    from repro.models import transformer as T

    untied = "lm_head" in w
    codes = _base(w, _crossbar, w["lm_head"] if untied else None)
    ads = {"prologue": [], "epilogue": [], "body": [{
        "mixer": {n: a["layers"][n] for n in _ATTN},
        "ffn": {n: a["layers"][n] for n in _FFN}}]}
    if untied:
        ads["lm_head"] = a["lm_head"]
    t_base = None
    if teacher is not None:
        tw = {"layers": {**w["layers"], **{n: teacher["layers"][n]
                                           for n in LINEARS}},
              "embed": w["embed"], "final_norm": w["final_norm"]}
        t_base = _base(tw, lambda x: x, teacher.get("lm_head"))
    want = jax.eval_shape(lambda k: T.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = {"base": t_base if t_base is not None else _base(
        w, lambda c: jax.ShapeDtypeStruct(c["g_pos"].shape, "bfloat16"),
        w.get("lm_head")), "adapters": ads}
    for part in ("base", "adapters"):
        ws = jax.tree_util.tree_structure(want[part])
        gs = jax.tree_util.tree_structure(got[part])
        if ws != gs:
            raise ValueError(f"{part} tree differs from the program's:\n"
                             f"{ws}\n{gs}")
        for x, y in zip(jax.tree_util.tree_leaves(want[part]),
                        jax.tree_util.tree_leaves(got[part])):
            if x.shape != y.shape:
                raise ValueError(f"{part} leaf shape {y.shape} != {x.shape}")
    return codes, ads, t_base
