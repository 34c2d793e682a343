"""Work the served model requires, from the configuration's sizes.

A linear layer's weights are int4 (half a byte each) with one float32
scale per output channel; the tied head reads the embedding in bfloat16,
as the model serves it; keys and values are bfloat16.  Operations are
2·M·K·N per GEMM and 4·heads·head_dim per query-key pair (scores and
values).  Nothing counts the traffic a kernel happens to make beyond this.
"""

from typing import Dict, List, Tuple


def linears(m: Dict) -> List[Tuple[int, int]]:
    """(K, N) of one layer's projections: q, k, v, o, up, gate, down."""
    D, H, KV, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D),
            (D, F), (D, F), (F, D)]


def kv_bytes_per_token(m: Dict) -> int:
    return m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * 2 * 2


def weight_bytes(m: Dict) -> int:
    """What one step must read of the weights: every int4 projection with
    its scales, and the bfloat16 head."""
    per_layer = sum(k * n // 2 + 4 * n for k, n in linears(m))
    return m["n_layers"] * per_layer + m["vocab"] * m["d_model"] * 2


def _attn_flops(m: Dict, pairs: float) -> float:
    return m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * pairs


def decode_flops(m: Dict, ctx: int) -> float:
    """One generated token that attends `ctx` keys."""
    lin = sum(k * n for k, n in linears(m))
    return (m["n_layers"] * 2 * lin + _attn_flops(m, ctx)
            + 2 * m["d_model"] * m["vocab"])


def prefill_flops(m: Dict, length: int) -> float:
    """A prompt of `length` tokens, causal, with the head on its last
    position."""
    lin = sum(k * n for k, n in linears(m))
    return (m["n_layers"] * 2 * lin * length
            + _attn_flops(m, length * (length + 1) / 2)
            + 2 * m["d_model"] * m["vocab"])


def decode_step_bytes(m: Dict, ctx: List[int]) -> float:
    """What a decode step over rows with these contexts must read: the
    weights once and each row's live keys and values."""
    return weight_bytes(m) + kv_bytes_per_token(m) * sum(ctx)
