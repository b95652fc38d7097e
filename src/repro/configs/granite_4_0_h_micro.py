"""granite-4.0-h-micro — hybrid Mamba-2 + attention
[hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid].

40L d_model=2048: 36 Mamba-2 mixers and 4 grouped-query attention layers
(indices 5, 15, 25, 35: four periods of [M x5, A, M x4]), each mixer with
its own SwiGLU MLP of width 8192. Attention: 32 q / 8 kv heads of 64, no
positional embedding (NoPE), scores scaled by 1/64. Mamba-2: 64 heads of
64, d_state 128, one B/C group, conv 4 with bias, SSD chunk 256, gated
RMSNorm. muP-style multipliers: embeddings x12, both residual branches of
every layer x0.22, logits / 8. Vocab 100352, tied embeddings, RMSNorm eps
1e-5. 3.19B parameters.
"""
from repro.configs.base import HybridConfig, LMConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = LMConfig(
    arch_id="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    norm_eps=1e-5,
    pos_emb="none",
    tie_embeddings=True,
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64, n_groups=1,
                  chunk_size=256),
    hybrid=HybridConfig(layer_types=_PERIOD * 4),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    max_seq_len=131_072,
)
