"""90th percentile, over every request due in the window, of first-token
time minus due time (end - due for a request still waiting), s."""
import harness as H


def read(run):
    return H.percentile(H.ttft_values(run), 90)
