"""Attention kernel, prefill: the flash-prefill calls, least time by
their work (bench/work/flash_prefill.py) over the time their ops took in
the traced window."""

from harness.layers import roofline_share

UNIT = "%"


def read(run):
    return roofline_share(run, "flash_prefill", "prefill")
