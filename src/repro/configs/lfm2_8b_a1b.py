"""LFM2-8B-A1B [hf:LiquidAI/LFM2-8B-A1B config.json]: 24 layers at d 2048,
18 gated short-convolution layers (kernel 3) beside 6 GQA layers (32q/8kv
heads of 64, QK-norm, rope theta 1e6); the first 2 layers have a dense
SwiGLU FFN of 7168, the rest 32 experts of 1792, top-4 of sigmoid scores
plus a selection-only expert bias, weights renormalised (routed scale 1);
vocab 65536, tied embeddings."""
from repro.configs.base import ArchConfig, MoEConfig

# the published `layer_types`, 'full_attention' as this repo's 'global'
_C, _A = "conv", "global"
LAYER_KINDS = (_C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A,
               _C, _C, _C, _A, _C, _C, _A, _C, _C)

CONFIG = ArchConfig(
    name="lfm2-8b-a1b",
    family="hybrid",
    n_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=7168,
    vocab_size=65536,
    ffn_type="swiglu",
    pattern=(_A, _C, _C, _C),
    layer_kinds=LAYER_KINDS,
    n_dense_layers=2,
    qk_norm=True,
    conv1d_width=3,
    rope_theta=1_000_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    moe=MoEConfig(n_experts=32, top_k=4, d_expert=1792, capacity_factor=0.0,
                  score="sigmoid", expert_bias=True),
)

SMOKE = CONFIG.with_overrides(
    dtype="float32",
    n_layers=8, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=192,
    vocab_size=512, crossbar_size=64, attn_chunk=64, n_microbatches=1,
    moe=MoEConfig(n_experts=16, top_k=4, d_expert=96, capacity_factor=0.0,
                  score="sigmoid", expert_bias=True, n_held=4),
)
