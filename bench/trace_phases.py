"""One traced run of one cell, read for the engine's phase spans and the
model's scopes (``bench/lib/phases.py``).

    python3 bench/trace_phases.py --workload <name> --seed <n> \
        --seconds <s> [--keep <dir>]

The run is that of ``bench/run.py --trace 1``: the same set-up, load,
ramp and profiler trace (``harness.TRACE_SECONDS`` from
``harness.TRACE_START_FRAC`` of the window).  It prints one JSON line:
the phase and scope numbers of ``phases.METRICS`` beside
``step.device_ms`` and the idle share; the window's idle time by the
innermost host span over it and its longest gaps so named; device ms
per step-program execution by scope; and copy-on-writes a second.
``--keep`` keeps the trace there, gzipped.  Exits 2 without an
accelerator.

It stands in for the benchmark's own traced run until
``bench/lib/harness.py`` reads ``phases.load`` itself (PERF.md, Open
questions); it goes then.
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from bench.lib import harness, phases, spec  # noqa: E402
from bench.lib.readers import STEP_MODULES, pct, tpots  # noqa: E402


def read(ph, recs, cows, seconds):
    """The printed numbers of one run: ``ph`` its ``phases.Phases``;
    ``cows`` the cache's ``n_cow`` as the window opened and closed."""
    red = ph.reduced
    n, dev_s = red.module_seconds(STEP_MODULES)
    metrics = {name: fn(ph) for name, fn in phases.METRICS.items()}
    metrics["step.device_ms"] = dev_s / n * 1e3 if n else None
    metrics["device.idle_share"] = (
        100.0 * (1.0 - red.busy_s / red.window_s) if red.chips else None)
    win = types.SimpleNamespace(window_recs=[r for r in recs
                                             if r.in_window])
    tpot = pct(tpots(win), 50)
    scopes = ph.by_scope()
    return {
        "metrics": metrics,
        "idle_by_span": ph.idle_by_span(),
        "idle_gaps": ph.top_gaps(),
        "step_scopes_ms": {k: s / n * 1e3 for k, s in sorted(
            scopes.items(), key=lambda kv: -kv[1])} if n else {},
        # the model's scopes and the loop's unscoped ops, as a share of
        # step-program time
        "scoped_share": (sum(s for k, s in scopes.items()
                             if k not in (phases.COPY, "other"))
                         / dev_s if n and scopes else None),
        "cow_per_s": (cows[1] - cows[0]) / seconds,
        "tpot_p50_ms": None if tpot is None else tpot * 1e3,
        "window_s": red.window_s,
    }


def main(argv=None, *, require_accelerator: bool = True, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the gzipped trace in")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload,
                          root=spec.ROOT if root is None else root)
    if harness.check_devices(cell, require_accelerator) is None:
        return 2
    import jax
    # the scopes are HLO metadata, which the persistent compile cache's
    # key leaves out by default: an executable cached from the same
    # program without them (or with others) would be loaded unscoped
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    runner = harness.Runner(cell, traced=True)
    runner.build(args.seed)
    backend = runner.backend
    cows = []
    tmp = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        on_trace, st = harness._trace_hook(args.seconds, True, tmp, runner)

        def on_tick(now):
            on_trace(now)
            if len(cows) < 2 and now >= (0.0 if not cows else args.seconds):
                cows.append(backend.stats()["n_cow"])
        out = runner.measure(args.seed, args.seconds, on_tick=on_tick)
        runner.free()
        xp = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                       recursive=True)
        if not xp or st["t1"] is None:
            raise RuntimeError("the window closed before the trace did")
        ph = phases.load(xp[0])
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            dst = os.path.join(args.keep, f"{args.workload}.{args.seed}"
                               ".xplane.pb.gz")
            with open(xp[0], "rb") as f, gzip.open(dst, "wb") as g:
                shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    cows += [cows[-1] if cows else 0] * (2 - len(cows))
    res = read(ph, out["recs"], cows, args.seconds)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, **res)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
