"""Scheduler chunked prefill: 90th percentile, over every request due
in the window and admitted in it, of admission to first token
(``Request.t_first - t_admit`` on the engine's host clock; end - t_admit
for a request with no token yet), s.  Requests never admitted are left
to ``queue_wait_p90_s``."""
import harness as H
import phases


def read(run):
    waits = phases.request_waits(run)
    if waits is None:
        return None
    return H.percentile([p for _, p in waits if p is not None], 90)
