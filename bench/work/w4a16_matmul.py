"""Work of one weight-only int4 GEMM call (kernels/w4a16_matmul.py):
x [M, K] bfloat16 times int4 W [K, N] with float32 per-channel scales,
out [M, N] bfloat16."""

from typing import Dict, Iterator, Tuple

from . import model

#: the HLO instruction name the device trace gives this kernel's ops
TRACE_NAMES = ("w4a16_matmul",)


def work(M: int, K: int, N: int) -> Tuple[float, float]:
    flops = 2.0 * M * K * N
    nbytes = K * N / 2 + 4 * N + 2 * M * K + 2 * M * N
    return flops, nbytes


def calls(m: Dict, rows: int, ctx=None) -> Iterator[Tuple[float, float]]:
    """The calls of one step whose GEMMs have `rows` rows (the decode
    batch bucket, or the padded prompt of one prefill)."""
    for _ in range(m["n_layers"]):
        for k, n in model.linears(m):
            yield work(rows, k, n)
