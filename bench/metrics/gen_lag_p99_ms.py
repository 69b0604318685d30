"""Load generator: 99th percentile of send time minus due time over the
requests due in the window, ms.  A late generator is not a fast server."""
import harness as H


def read(run):
    v = H.percentile([s.sent - s.due for s in run.sent if s.in_window], 99)
    return None if v is None else 1e3 * v
