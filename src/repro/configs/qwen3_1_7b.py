"""qwen3-1.7b [dense]: 28L d_model=2048 16H (GQA kv=8) d_ff=6144
vocab=151936 — qk_norm, GQA, tied embeddings. [hf:Qwen/Qwen3-1.7B config.json]"""
from repro.configs.shapes import ArchSpec, lm_shapes, FULL_ATTN_SKIP
from repro.core.dora import AdapterConfig
from repro.core.rram import RramConfig
from repro.models.attention import AttentionConfig
from repro.models.layers import MlpConfig
from repro.models.transformer import ModelConfig

_ADAPTER = AdapterConfig(rank=8, kind="dora")
_RRAM = RramConfig(relative_drift=0.10)

FULL = ModelConfig(
    name="qwen3-1.7b",
    d_model=2048,
    n_layers=28,
    vocab=151936,
    attn=AttentionConfig(
        d_model=2048, num_heads=16, num_kv_heads=8, head_dim=128,
        rope_theta=1e6, qk_norm=True,
    ),
    mlp=MlpConfig(d_model=2048, d_ff=6144, gated=True, activation="silu"),
    mixer_pattern=("attn",),
    ffn_pattern=("mlp",),
    norm="rms",
    tie_lm_head=True,
    adapter=_ADAPTER,
    rram=_RRAM,
)

SMOKE = ModelConfig(
    name="qwen3-smoke",
    d_model=64,
    n_layers=4,
    vocab=512,
    attn=AttentionConfig(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, qk_norm=True
    ),
    mlp=MlpConfig(d_model=64, d_ff=128, gated=True, activation="silu"),
    adapter=AdapterConfig(rank=4, kind="dora"),
    rram=RramConfig(relative_drift=0.10),
    remat=False,
)

ARCH = ArchSpec(
    name="qwen3-1.7b",
    full=FULL,
    smoke=SMOKE,
    shapes=lm_shapes(subquadratic=False),
    skips={"long_500k": FULL_ATTN_SKIP},
)
