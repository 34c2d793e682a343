"""`decode_step_ms.chat`, read in the qwen3-4b chat cell
by the same code."""

from harness.layers import load_metric

UNIT = "ms"


def read(run):
    return load_metric("decode_step_ms.chat").read(run)
