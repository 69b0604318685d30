"""95th percentile of the gaps between consecutive output tokens, ms."""
import harness as H


def read(run):
    v = H.percentile(H.itl_gaps(run), 95)
    return None if v is None else 1e3 * v
