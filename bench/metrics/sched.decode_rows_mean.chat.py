"""Scheduler: mean active decode rows per decode or fused dispatch
issued in the window (replay dispatches left out), where the cell is
judged on its latency tails: the occupancy at which its steps run."""
from bench.lib.readers import decode_rows_mean


def read(run):
    return decode_rows_mean(run)
