"""One run of one cell: set up, measure, check, report.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything happens in this one process: it holds the chip.  Set-up
makes the weights on the device from the seed, builds the backend that
``ServeOptions.build`` returns, and runs every step program once at
each shape the cell's traffic uses, from the persistent compile cache.
The cell's own load then runs untimed for its ``warm_s`` (the ramp),
so that the window finds the engine as full as that load keeps it; the
window offers the load for ``--seconds`` and follows every request due
in it to its end.  After it, the pages are freed and
a sample of the served streams is compared with the plain reference.

With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from the
requests' counters, a record of each dispatch, and a profiler trace of
a few seconds in the middle of the window.

The last line on standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, then ``compiles_in_window`` and, last, ``checks``:
each number compared beside its limit).  The checks are also the last
lines on standard error.  Without an accelerator, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import itertools
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from . import spec as spec_mod
from .drive import Recorder, Wrapped, run_window
from .traffic import Traffic, describe, prompt_bound, tokens_bound

PEAKS_FILE = spec_mod.BENCH_DIR / "peaks.json"
#: the profiler traces TRACE_SECONDS from TRACE_START_FRAC of the window
TRACE_START_FRAC = 0.4
TRACE_SECONDS = 3.0
#: ``correct``'s sample: about CHECK_TOKENS served tokens, from at most
#: CHECK_MAX_REQUESTS requests; fewer than CHECK_MIN_TOKENS is no pass
CHECK_TOKENS = 400
CHECK_MAX_REQUESTS = 8
CHECK_MIN_TOKENS = 200
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def peaks_for(kind: str, path=PEAKS_FILE) -> Dict[str, Any]:
    """The peaks of ``kind`` (a ``device_kind``); a device missing from
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


class Compiles:
    """Counts backend compiles and lowerings (jit cache misses)."""

    def __init__(self):
        self.compiles = 0
        self.lowerings = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
        elif event == _LOWER_EVENT:
            self.lowerings += 1

    def snapshot(self):
        return self.compiles, self.lowerings


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (bench/metrics/*.py)."""
    cell: Any
    seconds: float
    setup_s: float
    recs: List
    tokens_in_window: int
    chips: int
    end: float                               # client clock at the run's end
    dims: Any = None
    peak: Optional[Dict[str, Any]] = None
    dispatches: Optional[List] = None        # traced runs: every dispatch
    traced_host: Optional[tuple] = None      # (t0, t1) on the client clock
    trace: Any = None                        # trace.Reduced
    weight_bytes: int = 0

    @property
    def window_recs(self):
        return [r for r in self.recs if r.in_window]

    def traced_dispatches(self):
        t0, t1 = self.traced_host
        return [d for d in self.dispatches if t0 <= d.t0 and d.t1 <= t1]


def _serve_options(cell, max_tokens: int):
    from repro.serve import ServeOptions
    from repro.serve.kv_cache import pages_needed
    s = dict(cell.cell["serve"])
    s.setdefault("max_pages_per_seq",
                 pages_needed(max_tokens, s.get("page_size", 16)))
    return ServeOptions(**s)


def _engines(backend):
    return list(getattr(backend, "replicas", [backend]))


def warm_up(backend, programs, prompt_pages: int) -> int:
    """Run each step program once at every shape the cell uses, on
    every replica: decode (and verify) over the full batch, chunked
    prefill and the fused step at each context bucket a prompt of up to
    ``prompt_pages`` pages reaches.  Inputs are null-page routed, so no
    live page is written.  Returns the number of dispatches."""
    import jax
    import jax.numpy as jnp
    n = 0

    def once(out):
        # wait for it, then drop it: each output holds a whole pool
        nonlocal n
        jax.block_until_ready(out)
        n += 1

    for eng in _engines(backend):
        B, Bp, C = eng.max_batch, eng.prefill_batch, eng.chunk_size
        mpps = eng.cache.max_pages_per_seq
        state = {"k_pages": eng.cache.k_pages, "v_pages": eng.cache.v_pages,
                 "page_tables": jnp.zeros((B, mpps), jnp.int32),
                 "lengths": jnp.zeros((B,), jnp.int32)}
        widths = [1] + ([eng.spec_k + 1] if eng.spec_k else [])
        buckets = [e for e in eng.bucket_edges if e <= eng._bucket_pages(
            prompt_pages)]
        for T in widths:
            prog = programs.decode if T == 1 else programs.verify
            once(prog(eng.params, state, jnp.zeros((B, T), jnp.int32)))
        z = jnp.zeros((Bp,), jnp.int32)
        for nb in buckets:
            tbl = jnp.zeros((Bp, nb), jnp.int32)
            toks = jnp.zeros((Bp, C), jnp.int32)
            once(programs.chunk(
                eng.params, {"k_pages": state["k_pages"],
                             "v_pages": state["v_pages"]}, toks, tbl, z, z))
            for T in widths:
                once(programs.fused(
                    eng.params, state, jnp.zeros((B, T), jnp.int32), toks,
                    tbl, z, z))
        if eng.cache.prefix is not None:
            # copy-on-write of a page the prefix cache shares
            import numpy as np
            from repro.serve.kv_cache import _copy_page
            once(_copy_page(eng.cache.k_pages, np.int32(0), np.int32(0)))
    return n


def _trace_hook(seconds: float, traced: bool, tmp: str, runner):
    """Start the profiler at ``TRACE_START_FRAC`` of the window and stop
    it ``TRACE_SECONDS`` later; returns (on_tick, state).  Starting and
    stopping the profiler stalls the host for seconds; that stall is
    taken off the client clock, so it delays no request as the client
    sees it."""
    import jax
    start = seconds * TRACE_START_FRAC
    length = TRACE_SECONDS
    st = {"on": False, "done": not traced, "t0": None, "t1": None,
          "span": None}

    def on_tick(now: float):
        if st["done"]:
            return
        if not st["on"] and now >= start:
            with runner.paused():
                jax.profiler.start_trace(tmp)
            st["span"] = jax.profiler.TraceAnnotation("bench.traced")
            st["span"].__enter__()
            st["on"], st["t0"] = True, runner.clock()
        elif st["on"] and now >= start + length:
            st["t1"] = runner.clock()
            st["span"].__exit__(None, None, None)
            with runner.paused():
                jax.profiler.stop_trace()
            st["on"], st["done"] = False, True
    return on_tick, st


class Runner:
    """Set-up of one cell in this process: the model, its program
    bundle (compiled once, shared by every backend built here), and the
    compile counter.  ``build`` makes the weights for a seed and the
    backend over them, and warms it up."""

    def __init__(self, cell, *, traced: bool = False,
                 fault: Optional[Callable] = None):
        import jax
        from repro.launch.compile_cache import use_compile_cache
        from repro.models import build_model
        from repro.serve.step import ServePrograms
        from .weights import dims as dims_of, program_config
        log(f"compile cache {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.compiles = Compiles()
        self.cell = cell
        self.traced = traced
        self.k = dims_of(cell.config)
        self.model = build_model(program_config(cell.config))
        self._t0 = 0.0
        self._paused = 0.0
        wraps = [fault] if fault is not None else []
        self.recorder = None
        if traced:
            self.recorder = Recorder(self.k, self.clock)
            wraps.append(self.recorder.wrap)
        self.programs = Wrapped(ServePrograms(self.model), wraps)
        self.backend = None
        self.weights = None

    def clock(self) -> float:
        """The client clock: seconds since the window opened, less the
        time spent in ``paused`` blocks."""
        return time.perf_counter() - self._t0 - self._paused

    @contextlib.contextmanager
    def paused(self):
        """A block the client clock does not see (the profiler's own
        start and stop)."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self._paused += time.perf_counter() - t

    def build(self, seed: int) -> int:
        """Weights from ``seed``, the backend over them, warmed up.
        Returns the number of warm-up dispatches."""
        import jax
        from .weights import check_layout, make_weights
        self.free()
        self.weights = make_weights(self.k, seed, self.cell.config["dtype"])
        check_layout(self.weights, self.model.abstract())
        tp = self.cell.traffic
        opts = _serve_options(self.cell, tokens_bound(tp))
        self.backend = opts.build(self.model, self.weights,
                                  programs=self.programs)
        n = warm_up(self.backend, self.programs,
                    -(-prompt_bound(tp) // opts.page_size))
        jax.effects_barrier()
        if self.recorder is not None:
            self.recorder.reset(self._replays())
        return n

    def _replays(self) -> int:
        return int(self.backend.stats()["n_replay_steps"])

    def free(self) -> None:
        """Drop the backend and its page pools."""
        if self.backend is not None:
            for eng in _engines(self.backend):
                eng.cache.k_pages.delete()
                eng.cache.v_pages.delete()
        self.backend = None

    def measure(self, seed: int, seconds: float, *, rate=None,
                on_tick=None) -> Dict:
        """Offer the cell's load (or ``rate``) through the ramp, for
        ``seconds`` of window, and drain."""
        from repro.serve import Request
        load = self.cell.cell["load"]
        offline = load["mode"] == "offline"
        warm = float(load.get("warm_s", 0.0))
        if rate is None and not offline:
            rate = float(load["rate_rps"])
        traffic = Traffic(self.cell.traffic, seed=seed, vocab=self.k.V,
                          seconds=seconds, rate=0.0 if offline else rate,
                          warm=warm)
        source = iter(traffic)
        source = itertools.chain([next(source)], source)

        def make_request(s, due):
            return Request(rid=s.rid, prompt=s.prompt,
                           max_new_tokens=s.max_new, arrival=due)

        after_step = None
        if self.recorder is not None:
            def after_step():
                self.recorder.end_step(self._replays())
        c0 = self.compiles.snapshot()
        # the clock reads -warm as the ramp starts and 0 as the window opens
        ramp_start = time.perf_counter()
        self._t0, self._paused = ramp_start + warm, 0.0
        out = run_window(self.backend, source, make_request, seconds=seconds,
                         cap=float(self.cell.cell["drain_cap_s"]),
                         mode=load["mode"],
                         depth=int(load.get("queue_depth", 0)),
                         clock=self.clock, on_tick=on_tick,
                         after_step=after_step, traced=self.traced)
        c1 = self.compiles.snapshot()
        out["compiles"] = c1[0] - c0[0]
        out["lowerings"] = c1[1] - c0[1]
        out["ramp_start"] = ramp_start
        return out

    def judge(self, recs, seed: int, control: Optional[str] = None):
        """Compare a sample of the served streams with the reference
        (``correct``'s numbers); the page pools must be freed first.
        Returns (correct, checks, cmp).  With ``control`` (a precision)
        the control is judged by the same limits, its tokens in the
        served tokens' place: ``cmp["control_correct"]`` and
        ``cmp["control_checks"]``."""
        from . import oracle
        t = time.perf_counter()
        picked = oracle.sample(recs, seed, tokens=CHECK_TOKENS,
                               max_requests=CHECK_MAX_REQUESTS)
        cmp = oracle.compare(self.weights, self.k, picked, control=control)
        log(f"reference over {len(picked)} requests, {cmp['tokens']} served "
            f"tokens, in {time.perf_counter() - t:.3f} s")
        limit = float(self.cell.cell["check"]["max_logit_gap"])

        def verdict(gap: float, bad: int):
            checks = {
                "max_logit_gap": {"value": gap, "limit": limit},
                "bad_streams": {"value": bad, "limit": 0},
                # a floor: a sample too small to judge is no pass
                "sampled_tokens": {"value": cmp["tokens"],
                                   "limit": CHECK_MIN_TOKENS},
            }
            ok = (gap <= limit and bad == 0
                  and cmp["tokens"] >= CHECK_MIN_TOKENS)
            return ok, checks

        correct, checks = verdict(cmp["served"],
                                  oracle.bad_streams(recs, self.k.V))
        if control:
            # the control puts one token at each served position, so its
            # streams are whole by construction
            cmp["control_correct"], cmp["control_checks"] = verdict(
                cmp["control"], 0)
        return correct, checks, cmp


def check_devices(cell, require_accelerator: bool):
    """The devices, or None (after saying why) when the cell cannot run."""
    import jax
    devices = jax.devices()
    dev0 = devices[0]
    log(f"platform {dev0.platform} device_kind {dev0.device_kind} "
        f"count {len(devices)}")
    if require_accelerator and dev0.platform not in ("tpu", "gpu"):
        log(f"no accelerator: JAX found {dev0.platform}")
        return None
    if len(devices) < cell.chips:
        log(f"the cell needs {cell.chips} chips; JAX found {len(devices)}")
        return None
    return devices


def main(argv=None, *, t_start: Optional[float] = None,
         require_accelerator: bool = True, root=None,
         fault: Optional[Callable] = None, peaks: Optional[Dict] = None
         ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload,
                              root=spec_mod.ROOT if root is None else root)
    devices = check_devices(cell, require_accelerator)
    if devices is None:
        return 2
    dev0 = devices[0]
    traced = bool(args.trace)
    if traced and peaks is None:
        peaks = peaks_for(dev0.device_kind)

    from . import work
    runner = Runner(cell, traced=traced, fault=fault)
    n_warm = runner.build(args.seed)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    on_tick, tstate = _trace_hook(args.seconds, traced, tmp, runner)
    out = runner.measure(args.seed, args.seconds, on_tick=on_tick)
    # set-up ends where the load, ramp included, begins
    setup_s = out["ramp_start"] - t_start
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
    runner.free()                  # the reference runs after the pools go

    recs = out["recs"]
    win = [r for r in recs if r.in_window]
    failed = sum(1 for r in win if r.done is None)
    log(f"drew {len(win)} requests due in {args.seconds:g} s: "
        f"lengths {json.dumps(describe([r.spec for r in win]))}; "
        f"generator late by up to {out['lag_max'] * 1e3:.3f} ms; "
        f"window + drain {out['end']:.3f} s; {n_warm} warm-up dispatches; "
        f"{out['inflight_at_open']} requests in flight as the window opened")
    log(f"compiles after set-up (ramp, window, drain): {out['compiles']} "
        f"backend compiles, {out['lowerings']} lowerings")
    correct, checks, _ = runner.judge(recs, args.seed)
    correct = correct and len(win) > 0

    run = Run(cell=cell, seconds=args.seconds, setup_s=setup_s, recs=recs,
              tokens_in_window=out["tokens_in_window"], chips=cell.chips,
              end=out["end"],
              dims=runner.k, peak=peaks,
              weight_bytes=work.weight_bytes(runner.k))
    device: Dict[str, Any] = {"platform": dev0.platform,
                              "kind": dev0.device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": int(mem_peak)}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": len(win), "failed": failed}
    breakdown = None
    if traced:
        from .trace import load as load_trace
        run.dispatches = runner.recorder.dispatches()
        run.traced_host = (tstate["t0"], tstate["t1"])
        xp = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        if not xp or tstate["t1"] is None:
            raise RuntimeError("the window closed before the trace did; "
                               "lengthen --seconds or shorten the trace")
        run.trace = load_trace(xp[0])
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops(),
                     "idle_gaps": run.trace.top_gaps()}
    shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compiles_in_window"] = out["compiles"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
