"""Whole model step: operations the model requires for the traced
window's tokens (bench/work/model.py; prompts unpadded), over the window
at the chip's bfloat16 peak."""

from harness.layers import mfu

UNIT = "%"


def read(run):
    return mfu(run)
