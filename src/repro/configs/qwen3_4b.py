"""qwen3-4b [dense] — GQA + qk_norm.

36L d_model=2560 32H (GQA kv=8) d_ff=9728 vocab=151936.
Tied head. Source: hf:Qwen/Qwen3-4B config.json. [hf tier]
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="qwen3-4b",
    family="dense",
    n_layers=36,
    d_model=2560,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab=151936,
    qk_norm=True,
    rope="rope",
    tie_embeddings=True,
    rope_theta=1000000.0,
    source="hf:Qwen/Qwen3-4B [hf]",
)
