"""Set-up: process start to the window's start (imports, weights,
compile or compile-cache load, primer, warm-up), host clock."""


def read(run):
    return run.setup_s
