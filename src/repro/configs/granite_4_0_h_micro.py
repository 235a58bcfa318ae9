"""granite-4.0-h-micro [hybrid]: 40 layers in four periods of nine Mamba2
mixers and one attention mixer (attention at position 5 of each 10), a
SwiGLU MLP after every mixer, no position encoding
[hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid].
d_model=2048; attention GQA 32/8 heads x 64 with softmax scale
``attention_multiplier`` 1/64; Mamba2 64 heads x 64 (d_inner 4096),
state 128, one group, conv 4 with bias; MLP 8192; vocab 100,352, tied.
The residual branches are scaled by 0.22, the embedding by 12 and the
logits by 1/8."""
from .base import ArchConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100352,
    ssm_state=128,
    ssm_heads=64,
    ssm_conv=4,
    ssm_expand=2,
    layer_pattern=PERIOD,
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
