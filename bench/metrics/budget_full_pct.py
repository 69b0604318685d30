"""Scheduler: share of the window's engine steps whose schedule left
prompt tokens waiting because the token budget ran out (a slice cut
only by the chunk does not count; engine counters budget_full_steps /
steps), %."""


def read(run):
    if "budget_full_steps" not in run.counters1:
        return None
    steps = run.counter("steps")
    return None if steps <= 0 else \
        100.0 * run.counter("budget_full_steps") / steps
