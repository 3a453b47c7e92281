#!/usr/bin/env python
"""What the serve engine's phase spans cost with no profiler session.

    python scripts/span_cost.py [--n 200000] [--per-step 8]

Times ``--n`` entries and exits of ``telemetry.phase`` (a
``jax.profiler.TraceAnnotation``) with no profiler session running,
against an empty ``with`` block, and prints one JSON line: ns a span
over the empty block and µs a step at ``--per-step`` spans (the
spans a fused engine step with no drafting opens: ``serve.step``,
``serve.admit``, ``serve.headroom``, ``serve.inputs`` twice,
``serve.dispatch``, ``serve.wait`` and ``serve.bank``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.serve.telemetry import phase  # noqa: E402


def _best(fn, n: int, rounds: int = 5) -> float:
    """The fastest of ``rounds`` timings of ``n`` calls, in seconds."""
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        fn(n)
        best = min(best, time.perf_counter() - t)
    return best


def _spans(n: int) -> None:
    for _ in range(n):
        with phase("serve.step"):
            pass


def _empty(n: int) -> None:
    null = contextlib.nullcontext()
    for _ in range(n):
        with null:
            pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--per-step", type=int, default=8)
    args = ap.parse_args(argv)
    _spans(10)                                  # imports jax, warms up
    ns = max(_best(_spans, args.n) - _best(_empty, args.n), 0.0) \
        / args.n * 1e9
    print(json.dumps({"ns_per_span": ns, "spans_per_step": args.per_step,
                      "us_per_step": ns * args.per_step * 1e-3}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
