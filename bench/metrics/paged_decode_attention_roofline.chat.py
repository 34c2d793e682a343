"""Attention kernel, decode: the fused paged decode-attention calls,
least time by their work (bench/work/paged_decode_attention.py: the live
keys and values) over the time their ops took in the traced window."""

from harness.layers import roofline_share

UNIT = "%"


def read(run):
    return roofline_share(run, "paged_decode_attention", "decode")
