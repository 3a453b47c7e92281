"""Process start to the first due request: imports, device, weights,
compile-cache loads and the warm-up dispatches (host clock)."""


def read(run):
    return run.setup_s
