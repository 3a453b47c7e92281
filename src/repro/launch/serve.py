"""Serving launcher: continuous-batching engine over the paged KV
cache (default), the async streaming front-end (--stream), or the
naive lockstep loop (--naive) for comparison.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --requests 16 --batch 8 --prompt-len 64 --gen 32 --rate 50

Distributed serving: ``--tp N`` shards every engine over an N-device
mesh (CPU dev: XLA_FLAGS=--xla_force_host_platform_device_count=N);
``--replicas M`` puts M engine replicas behind the request router
(``--router-policy prefix|least-loaded|round-robin``).  The two
compose.  ``--max-replicas N`` makes the fleet *elastic* instead:
replicas scale between ``--min-replicas`` and N with demand (control
round every ``--scale-interval`` steps), live requests migrating off
draining replicas with token streams unchanged.  ``--stream`` serves the same trace through ``ServeFrontend``
instead: per-request token streams, SLO classes (every 4th request is
interactive), and ``--tenant-weights`` fair sharing.  Engine knobs
(chunk size, page size, context buckets, prefix sharing) are
consolidated in ``repro.serve.ServeOptions`` and documented in
docs/serving.md.
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import numpy as np

from repro import configs
from repro.data.pipeline import SyntheticPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serve import Request, ServeOptions
from repro.serve.kv_cache import pages_needed
from repro.serve.step import make_decode_step, make_prefill_step


def synth_requests(cfg, n: int, prompt_len: int, gen: int,
                   rate: float, seed: int = 0, prefix_len: int = 0):
    """Poisson arrival trace with markov-ish prompts (same generator
    family as the training pipeline).  ``prefix_len`` > 0 prepends one
    shared system-prompt prefix to every request (the prefix-cache
    benchmark shape)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))

    def walk(length):
        base = rng.integers(0, cfg.vocab_size)
        drift = rng.integers(0, 17, size=length)
        return ((base + np.cumsum(drift)) % cfg.vocab_size).astype(np.int32)

    # draw the prefix only when asked, so prefix_len=0 traces stay
    # draw-for-draw identical to earlier benchmarks at the same seed
    prefix = walk(prefix_len) if prefix_len else None
    reqs = []
    for i in range(n):
        prompt = walk(prompt_len)
        if prefix is not None:
            prompt = np.concatenate([prefix, prompt])
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=gen,
                            arrival=float(arrivals[i])))
    return reqs


def serve_trace(opts: ServeOptions, model, params, reqs, *,
                realtime: bool = True, smoke: bool = False):
    """Serve ``reqs`` on the backend ``opts`` describes and return the
    aggregate stats dict the CLI prints (throughput, TTFT, dispatch
    and cache-reuse counters).  With ``opts.trace_out`` set, the run's
    telemetry (spans + step timeline + metrics) lands there as JSONL
    (scripts/trace_report.py reads it)."""
    front = opts.build(model, params, smoke=smoke)
    out = _drive(front, reqs, realtime=realtime)
    _write_trace(opts, front, realtime=realtime)
    return out


def _write_trace(opts: ServeOptions, backend, *, realtime: bool) -> None:
    tel = getattr(backend, "tel", None)
    if tel is None or not opts.trace_out:
        return
    tel.clock_label = "seconds" if realtime else "steps"
    tel.write_jsonl(opts.trace_out)
    print(f"telemetry: wrote {opts.trace_out}")


def _drive(front, reqs, *, realtime: bool):
    """Run the trace and aggregate counters through the backend's own
    ``stats()`` — the ``ServeBackend`` contract every backend (engine,
    router, elastic controller) implements.  Summing over the live
    replica list here would silently drop the work of replicas that
    left an elastic fleet mid-trace; the protocol's stats fold departed
    replicas in."""
    t0 = time.perf_counter()
    done = front.run(reqs, realtime=realtime)
    dt = time.perf_counter() - t0
    st = front.stats()
    # the (possibly routed, possibly elastic) fleet behind the front:
    # per-engine breakdowns read the live members
    router = getattr(front, "router", front)
    engines = getattr(router, "replicas", [front])
    toks = sum(len(r.generated) for r in done)
    ttfts = [r.ttft for r in done if r.ttft is not None
             and r.ttft != float("inf")]
    return {"tokens": toks, "wall_s": dt,
            "tok_per_s": toks / max(dt, 1e-9),
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else float("nan"),
            "decode_steps": st["n_decode_steps"],
            "fused_dispatches": st["n_fused_dispatches"],
            "total_dispatches": st["n_total_dispatches"],
            "prefill_chunks": st["n_prefill_chunks"],
            "prefill_dispatches": st["n_prefill_dispatches"],
            "prefill_rows_mean": st["prefill_rows_mean"],
            "engine_stats": [e.stats() for e in engines],
            "shared_tokens": st["n_shared_tokens"],
            "cow_copies": st["n_cow"],
            "spec_rounds": st["n_spec_rounds"],
            "drafted": st["n_drafted"],
            "draft_accepted": st["n_draft_accepted"],
            # derived by telemetry.merge_stats inside stats() — the
            # same formula per replica and fleet-wide
            "accept_rate": st["accept_rate"],
            "dispatched": list(getattr(router, "n_dispatched",
                                       [len(done)])),
            "affinity_hits": int(st.get("n_affinity_hits", 0)),
            # elastic-fleet counters (0 on fixed backends)
            "replicas_peak": int(st.get("n_replicas_peak",
                                        len(engines))),
            "scale_ups": int(st.get("n_scale_ups", 0)),
            "scale_downs": int(st.get("n_scale_downs", 0)),
            "migrations": int(st.get("n_migrations", 0))}


def run_engine(model, params, reqs, *, batch, page_size, n_pages,
               realtime, chunk_size=32, prefill_batch=1,
               prefix_sharing=True,
               bucket_edges=None, spec_k=0, drafter_factory=None,
               tp=1, replicas=1, router_policy="prefix"):
    """Deprecated: build a ``repro.serve.ServeOptions`` and call
    ``serve_trace`` (or ``opts.build(...).run(...)``) instead.  Kept
    for one release as a kwargs-compatible shim."""
    warnings.warn("run_engine is deprecated; use ServeOptions + "
                  "serve_trace", DeprecationWarning, stacklevel=2)
    opts = ServeOptions(batch=batch, page_size=page_size,
                        n_pages=n_pages, chunk_size=chunk_size,
                        prefill_batch=prefill_batch,
                        prefix_sharing=prefix_sharing,
                        bucket_edges=bucket_edges, spec_k=spec_k,
                        tp=tp, replicas=replicas,
                        router_policy=router_policy)
    front = opts.sized_for(reqs).build(model, params)
    if drafter_factory is not None and spec_k:
        # the shim predates ServeOptions.draft_config: splice the
        # caller's factory into the already-built backend
        for e in getattr(front, "replicas", [front]):
            e.drafter = drafter_factory()
    return _drive(front, reqs, realtime=realtime)


def run_stream(opts: ServeOptions, model, params, reqs, *,
               smoke: bool = False):
    """Serve the trace through the async front-end: submit each
    request when its arrival time comes due (wall clock), pump until
    every stream completes, and report per-SLO-class TTFT plus the
    per-tenant token split.  Every 4th request is interactive; tenants
    rotate round-robin through ``--tenant-weights`` names."""
    fe = opts.build_frontend(model, params, smoke=smoke, realtime=True)
    tenants = list(opts.tenant_weights) or ["default"]
    pending = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    streams = {}
    t0 = time.perf_counter()
    while pending or fe.busy:
        now = time.perf_counter() - t0
        while pending and pending[0].arrival <= now:
            r = pending.pop(0)
            r.tenant = tenants[r.rid % len(tenants)]
            r.slo_class = "interactive" if r.rid % 4 == 0 else "batch"
            streams[r.rid] = fe.submit_request(r)
        if not fe.pump() and pending:
            time.sleep(max(0.0, pending[0].arrival
                           - (time.perf_counter() - t0)))
    dt = time.perf_counter() - t0
    done = fe.completed
    toks = sum(len(r.generated) for r in done)
    print(f"stream: {len(done)} streams, {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s)")
    for cls in ("interactive", "batch"):
        ts = [r.ttft for r in done
              if r.slo_class == cls and r.ttft is not None]
        if ts:
            print(f"  {cls:<12} n={len(ts):<3} "
                  f"TTFT mean {np.mean(ts) * 1e3:.0f} ms "
                  f"p99 {np.percentile(ts, 99) * 1e3:.0f} ms")
    st = fe.stats()
    shares = {t: st[f"tenant_tokens[{t}]"] for t in tenants
              if f"tenant_tokens[{t}]" in st}
    if len(shares) > 1:
        print("  tenant tokens: "
              + ", ".join(f"{t}={int(v)}" for t, v in shares.items()))
    print(f"  {int(st['n_slo_preemptions'])} SLO preemptions, "
          f"{int(st['n_cancelled'])} cancelled")
    _write_trace(opts, fe, realtime=True)


def run_naive(model, params, cfg, args):
    batch = SyntheticPipeline(cfg, batch=args.batch,
                              seq=args.prompt_len).device_batch(0)
    # decode headroom: without max_len the cache has prompt-length
    # capacity and decode writes clamp onto the last slot (wrong tokens)
    prefill = jax.jit(make_prefill_step(
        model, max_len=args.prompt_len + args.gen))
    step = jax.jit(make_decode_step(model))
    t0 = time.time()
    last, cache = prefill(params, batch)
    tok = np.argmax(np.asarray(last), -1).astype(np.int32)[:, None]
    t_prefill = time.time() - t0
    out = [tok]
    t0 = time.time()
    tok = jax.numpy.asarray(tok)
    for _ in range(args.gen - 1):
        tok, cache = step(params, cache, tok)
        out.append(np.asarray(tok))
    dt = time.time() - t0
    gen = np.concatenate(out, axis=1)
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decoded {args.gen} tokens/seq in {dt:.2f}s "
          f"({args.batch * args.gen / max(dt, 1e-9):.1f} tok/s)")
    print("generated ids (first seq):", gen[0][:16], "...")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--naive", action="store_true",
                    help="lockstep greedy loop instead of the engine")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by every "
                         "request (exercises the prefix cache)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--stats", action="store_true",
                    help="dump per-engine counter stats (dispatches, "
                         "co-ingestion occupancy, cache reuse) after "
                         "the run")
    ServeOptions.add_cli(ap)
    args = ap.parse_args()
    use_compile_cache()

    cfg = (configs.get_smoke if args.smoke else configs.get)(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.naive:
        run_naive(model, params, cfg, args)
        return

    reqs = synth_requests(cfg, args.requests, args.prompt_len, args.gen,
                          args.rate, prefix_len=args.shared_prefix)
    opts = ServeOptions.from_args(args).sized_for(
        reqs, shared_prefix=args.shared_prefix)

    if args.stream:
        run_stream(opts, model, params, reqs, smoke=args.smoke)
        return

    stats = serve_trace(opts, model, params, reqs, realtime=True,
                        smoke=args.smoke)
    spec_note = (f"{stats['spec_rounds']} verify rounds, "
                 f"accept rate {stats['accept_rate']:.2f} "
                 f"({stats['draft_accepted']}/{stats['drafted']} drafts), "
                 if opts.spec_k else "")
    dist_note = ""
    if opts.tp > 1 or opts.replicas > 1 or opts.max_replicas > 0:
        dist_note = (f"tp={opts.tp} x {opts.replicas} replica(s) "
                     f"[{opts.router_policy}] "
                     f"dispatched {stats['dispatched']}, "
                     f"{stats['affinity_hits']} affinity hits, ")
    if opts.max_replicas > 0:
        dist_note += (f"elastic {opts.min_replicas}.."
                      f"{opts.max_replicas} (peak "
                      f"{stats['replicas_peak']}, "
                      f"{stats['scale_ups']} up / "
                      f"{stats['scale_downs']} down, "
                      f"{stats['migrations']} migrations), ")
    print(f"{args.requests} requests ({args.shared_prefix}+"
          f"{args.prompt_len}+{args.gen} tok) "
          f"batch={opts.batch} pages={opts.n_pages}x{opts.page_size}: "
          f"{stats['tok_per_s']:.1f} tok/s, "
          f"TTFT {stats['ttft_mean_s'] * 1e3:.0f} ms, "
          f"{dist_note}"
          f"{stats['decode_steps']} decode steps, "
          f"{spec_note}"
          f"{stats['prefill_chunks']} prefill chunks in "
          f"{stats['prefill_dispatches']} dispatches "
          f"({stats['prefill_rows_mean']:.2f} rows/dispatch), "
          f"{stats['fused_dispatches']}/{stats['total_dispatches']} "
          f"launches fused, "
          f"{stats['shared_tokens']} prefix tokens reused, "
          f"{stats['cow_copies']} COW copies")
    if args.stats:
        for i, es in enumerate(stats["engine_stats"]):
            print(f"engine[{i}] stats: "
                  + ", ".join(f"{k}={v:.2f}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in es.items()))


if __name__ == "__main__":
    main()
