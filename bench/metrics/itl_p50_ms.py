"""Median gap between consecutive output tokens in the window, ms."""
import harness as H


def read(run):
    v = H.percentile(H.itl_gaps(run), 50)
    return None if v is None else 1e3 * v
