"""granite-4.0-h-micro [granite] — IBM Granite 4.0-H Micro (3B): 40 layers
in a period of 10 (9 Mamba2 mixers, GQA attention at layers 5, 15, 25 and
35); d_model=2048, 32 query heads over 8 KV heads (NoPE), SwiGLU d_ff=8192
after every mixer, Mamba2 d_state=128 with 64 heads of 64, chunk 256;
tied vocab 100352.
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
"""
from repro.models.granite import ATTENTION, MAMBA, GraniteConfig

_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4

CONFIG = GraniteConfig(
    arch_id='granite-4.0-h-micro',
    family='granite',
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    ssm_state=128,
    ssm_headdim=64,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    rms_norm_eps=1e-5,
)
