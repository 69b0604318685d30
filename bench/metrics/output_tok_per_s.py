"""Output tokens delivered inside the window over the window's length."""


def read(run):
    return len(run.window_tokens()) / run.seconds
