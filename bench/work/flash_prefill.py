"""Work of one flash-prefill call (kernels/paged_attention.py): causal
attention of a padded prompt of S tokens over itself, bfloat16 q, k, v
and out.  Causal: S(S+1)/2 query-key pairs."""

from typing import Dict, Iterator, Tuple

#: the HLO instruction name the device trace gives this kernel's ops
TRACE_NAMES = ("flash_prefill",)


def work(m: Dict, S: int) -> Tuple[float, float]:
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4.0 * H * hd * S * (S + 1) / 2
    nbytes = S * hd * (2 * H + 2 * KV) * 2
    return flops, nbytes


def calls(m: Dict, S: int, ctx=None) -> Iterator[Tuple[float, float]]:
    """One call per layer of a prefill of padded length S."""
    for _ in range(m["n_layers"]):
        yield work(m, S)
