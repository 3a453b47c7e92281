"""Step programs: device milliseconds per dispatch of the serve step
programs in the traced part of the window (profiler trace)."""
from bench.lib.readers import STEP_MODULES


def read(run):
    n, s = run.trace.module_seconds(STEP_MODULES)
    return s / n * 1e3 if n else None
