"""The whole step program as one kernel: the least time its dispatches
need (the larger of operations over peak FLOP/s and bytes over HBM
bandwidth, counted from shapes by the architecture module's
``dispatch_work``, the weights read once a dispatch) over the
device time they took, in the traced part of the window, in percent."""
from bench.lib.readers import STEP_MODULES
from bench.lib.work import least_seconds


def read(run):
    n, dev_s = run.trace.module_seconds(STEP_MODULES)
    ds = run.traced_dispatches()
    if not n or not ds or dev_s <= 0:
        return None
    least = sum(least_seconds(d.work, run.peak) for d in ds)
    # per-dispatch means: the host's and the trace's edges differ by a
    # dispatch at most
    return 100.0 * (least / len(ds)) / (dev_s / n)
