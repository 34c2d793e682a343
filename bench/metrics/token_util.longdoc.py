"""Scheduler: useful rows over all rows computed (prefill rows padded to
their prompt bucket, decode rows padded to their batch bucket), from the
engine's counters over the window."""

UNIT = "%"


def read(run):
    c0, c1 = run.record.counters0, run.record.counters1
    used = c1["n_tokens_packed"] - c0["n_tokens_packed"]
    wasted = c1["n_tokens_wasted"] - c0["n_tokens_wasted"]
    return 100.0 * used / (used + wasted) if used else None
