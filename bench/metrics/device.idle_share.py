"""Device: 1 - (union of the intervals in which an operation ran) /
(traced window), averaged over the chips, in percent."""


def read(run):
    if not run.trace.chips:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
