"""Scheduler: 95th percentile of the wait from a request's due time to its
admission (the engine's ``Request.t_admit``, on the clock the harness
gives it), over the requests due in the window.  A request never admitted
waits until the run's last stamp."""

from harness.stats import percentile

UNIT = "ms"


def read(run):
    rec = run.record
    waits = [((t.req.t_admit if t.req.t_admit is not None else rec.end)
              - t.due) * 1e3 for t in rec.counted()]
    return percentile(waits, 95) if waits else None
