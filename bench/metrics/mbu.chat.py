"""Whole model step: bytes the traced window's decode steps must read
(the weights once per step, each row's live keys and values;
bench/work/model.py), over the window at the chip's HBM bandwidth."""

UNIT = "%"


def read(run):
    from harness.layers import load_work

    if run.trace is None or run.peaks is None:
        return None
    model = load_work("model")
    nbytes = sum(model.decode_step_bytes(run.m, s.decode_ctx)
                 for s in run.traced_steps() if s.decode_ctx)
    if not nbytes:
        return None
    return 100.0 * nbytes / (run.trace.window_s * run.peaks["hbm_bytes_s"])
