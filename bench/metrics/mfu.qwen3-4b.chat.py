"""`mfu.chat`, read in the qwen3-4b chat cell
by the same code."""

from harness.layers import load_metric

UNIT = "%"


def read(run):
    return load_metric("mfu.chat").read(run)
