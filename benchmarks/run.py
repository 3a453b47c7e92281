"""Benchmark harness entry point: one module per paper table/figure,
plus the serving-throughput benchmarks.

    PYTHONPATH=src python -m benchmarks.run           # everything
    PYTHONPATH=src python -m benchmarks.run table6    # one benchmark
    PYTHONPATH=src python -m benchmarks.run --smoke   # CI: reduced sizes

``--smoke`` runs every benchmark at reduced problem size (benches whose
``run`` accepts a ``smoke`` kwarg) and fails loudly if any entry point
errors — the CI guard against perf entry points silently rotting.

A benchmark whose environment requirements aren't met (devices, deps)
raises ``common.Skip(reason)``; the summary prints the reason instead
of hiding the benchmark — a gate that didn't run must be visible.
"""
from __future__ import annotations

import inspect
import sys
import time

from .common import Skip, save
from . import (fig11_util, fig13_traffic, fig15_energy, fig19_sparse,
               fig22_simd, fig23_scaling, kernel_dataflow, roofline,
               serve_elastic, serve_faults, serve_fused, serve_prefill,
               serve_prefix, serve_router, serve_slo, serve_spec,
               serve_throughput, table5_cisc, table6_static)

BENCHES = {
    "table5": table5_cisc.run,
    "table6": table6_static.run,
    "fig11": fig11_util.run,
    "fig13": fig13_traffic.run,
    "fig15": fig15_energy.run,
    "fig19": fig19_sparse.run,
    "fig22": fig22_simd.run,
    "kernel": kernel_dataflow.run,
    "roofline": roofline.run,
    "serve": serve_throughput.run,
    "serve_prefix": serve_prefix.run,
    "serve_prefill": serve_prefill.run,
    "serve_fused": serve_fused.run,
    "serve_spec": serve_spec.run,
    "serve_router": serve_router.run,
    "serve_slo": serve_slo.run,
    "serve_elastic": serve_elastic.run,
    "serve_faults": serve_faults.run,
    "fig23": fig23_scaling.run,
}


def _metrics(out: dict) -> dict:
    """Scalar metrics worth tracking across PRs (gates are reported
    separately; tables and token dumps are noise at trend granularity).
    The labelled telemetry registry rides along under its own
    ``metrics_snapshot`` key — serve benches attach it via
    ``common.metrics_snapshot`` — kept intact, not flattened into the
    scalar trend."""
    m = {k: v for k, v in (out or {}).items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}
    snap = (out or {}).get("metrics_snapshot")
    if snap:
        m["metrics_snapshot"] = snap
    return m


def main(argv):
    smoke = "--smoke" in argv
    unknown = [a for a in argv if a.startswith("--") and a != "--smoke"]
    if unknown:
        print(f"unknown flags: {unknown}; known: --smoke", file=sys.stderr)
        return 2
    names = [a for a in argv if not a.startswith("--")] or list(BENCHES)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    summary = []
    for name in names:
        t0 = time.time()
        try:
            kw = {}
            if smoke and "smoke" in inspect.signature(
                    BENCHES[name]).parameters:
                kw["smoke"] = True
            out = BENCHES[name](**kw)
            checks = {k: v for k, v in (out or {}).items()
                      if isinstance(v, bool)}
            ok = all(checks.values()) if checks else True
            summary.append((name, "ok" if ok else "CHECK-FAILED",
                            time.time() - t0, checks, _metrics(out)))
        except Skip as s:
            summary.append((name, f"SKIPPED: {s.reason}",
                            time.time() - t0, {}, {}))
        except Exception as e:                      # noqa: BLE001
            import traceback
            traceback.print_exc()
            summary.append((name, f"ERROR: {e}", time.time() - t0, {}, {}))
    print("\n==================== summary ====================")
    failed = 0
    for name, status, dt, checks, _ in summary:
        skipped = status.startswith("SKIPPED")
        flag = "" if status == "ok" or skipped else "  <<<<"
        print(f"{name:12s} {status:14s} {dt:7.1f}s {checks}{flag}")
        if status != "ok" and not skipped:
            failed += 1
    print(f"{len(summary) - failed}/{len(summary)} benchmarks clean")
    # machine-readable perf trajectory: one consolidated file per run
    # (per-benchmark JSONs remain the detailed record) so cross-PR
    # tooling reads one artifact instead of re-deriving the roll-up
    save("summary", {
        "smoke": smoke,
        "benchmarks": {
            name: {"status": status, "seconds": round(dt, 2),
                   "gates": checks, "metrics": metrics}
            for name, status, dt, checks, metrics in summary}})
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
