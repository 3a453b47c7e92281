"""Median over finished requests due in the window of (last token time
- first token time) / (tokens - 1) (host clock)."""
from bench.lib.readers import pct, tpots


def read(run):
    v = pct(tpots(run), 50)
    return None if v is None else v * 1e3
