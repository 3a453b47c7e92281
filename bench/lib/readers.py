"""Small helpers the metric readers in ``bench/metrics/`` share."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

#: the serve step programs, by their jit names in a device trace
STEP_MODULES = ("jit_paged_step", "jit_chunk_step", "jit_fused_step",
                "jit_verify_step")


def pct(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None if empty."""
    return float(np.percentile(values, q)) if values else None


def tpots(run) -> List[float]:
    """Per finished in-window request with two or more tokens:
    (last token time - first token time) / (tokens - 1), in seconds."""
    return [(r.last - r.first) / (len(r.tokens) - 1)
            for r in run.window_recs
            if r.done is not None and len(r.tokens) > 1]


def decode_rows_mean(run) -> Optional[float]:
    """Mean active decode rows per decode, verify or fused dispatch
    issued in the window, replays left out."""
    rows = [d.rows for d in run.dispatches
            if d.kind != "chunk" and not d.replay and d.rows
            and 0.0 <= d.t0 <= run.seconds]
    return sum(rows) / len(rows) if rows else None
