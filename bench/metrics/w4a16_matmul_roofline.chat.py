"""GEMM kernel, decode regime: the w4a16 GEMM calls inside the decode
jit, least time by their work (bench/work/w4a16_matmul.py) over the time
their ops took in the traced window."""

from harness.layers import roofline_share

UNIT = "%"


def read(run):
    return roofline_share(run, "w4a16_matmul", "decode")
