"""90th percentile of the per-request time per output token (see
``tpot_p50_ms``): decode slowed by prefill riding in fused steps."""
from bench.lib.readers import pct, tpots


def read(run):
    v = pct(tpots(run), 90)
    return None if v is None else v * 1e3
