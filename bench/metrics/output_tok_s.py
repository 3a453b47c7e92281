"""Output tokens delivered inside the window, of any request, over the
window's seconds (the fleet's total where there are replicas)."""


def read(run):
    return run.tokens_in_window / run.seconds
