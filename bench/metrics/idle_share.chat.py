"""Device: share of the traced window in which no op ran on the chip
(one minus the union of the op intervals over the window)."""

from harness.layers import idle_share

UNIT = "%"


def read(run):
    return idle_share(run)
