"""Work of one fused paged decode-attention call
(kernels/paged_attention.py): one query token per row against that row's
live keys and values in the page pool (bfloat16).  Rows past the batch
(padding, context 0) read nothing."""

from typing import Dict, Iterator, List, Tuple

#: the HLO instruction name the device trace gives this kernel's ops
TRACE_NAMES = ("paged_decode_attention",)


def work(m: Dict, ctx: List[int], rows: int) -> Tuple[float, float]:
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    keys = sum(ctx)
    flops = 4.0 * H * hd * keys
    nbytes = keys * KV * hd * 2 * 2 + rows * H * hd * 2 * 2
    return flops, nbytes


def calls(m: Dict, rows: int,
          ctx: List[int]) -> Iterator[Tuple[float, float]]:
    """One call per layer of a decode step over `rows` bucket rows."""
    for _ in range(m["n_layers"]):
        yield work(m, ctx, rows)
