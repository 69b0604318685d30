"""Scheduler: real tokens scheduled over device-grid rows launched in
the window (engine counters scheduled_tokens / grid_tokens), %."""


def read(run):
    grid = run.counter("grid_tokens")
    return None if grid <= 0 else 100.0 * run.counter("scheduled_tokens") / grid
