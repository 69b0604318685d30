"""Scheduler admission: 90th percentile, over every request due in the
window, of its wait for a slot (``Request.t_admit - t_submit`` on the
engine's host clock; end - t_submit for a request still queued), s."""
import harness as H
import phases


def read(run):
    waits = phases.request_waits(run)
    return None if waits is None else H.percentile([w for w, _ in waits],
                                                   90)
