"""Drive qwen3-0.6b's serve path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: kernels, then serve
    python chip_smoke.py --four-chips   # four chips: TP=4 vs tp=1, and
                                        # four router replicas

Runs in this one process (no children) from the root of a checkout; it
puts ``src`` on the path itself.  Weights are random from a seed: the
repo holds no real ones.  Exits non-zero, with no ``ok`` line, when JAX
finds no TPU or when any phase fails.  The lines before the last one
report device kind, compile and wall seconds per phase, peak device
bytes and the oracle comparisons; none of them is a benchmark metric.
The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-0.6b"
#: kernel outputs vs their f32-accumulating refs, on bf16 operands: the
#: bound of the interpret-mode kernel tests
KERNEL_TOL = 3e-2
#: An engine stream may leave the greedy oracle only at a near-tie: the
#: oracle's top-2 logit gap there must be below this share of its top
#: logit's magnitude.  Hidden states are bf16 (8 significant bits, a
#: relative step of 2^-8); two programs that sum in another order may
#: differ by a few such steps after 28 layers, so a gap under four
#: steps (2^-6) is a tie the chip may break either way.
GAP_TOL = 2.0 ** -6

_compile_s = [0.0]


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs


def phase(name, fn, *args):
    """Run one phase; print its compile and wall seconds and the peak
    device bytes so far.  Exceptions propagate: a failed phase fails the
    script."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{name}] compile {_compile_s[0] - c0:.2f}s wall {wall:.2f}s "
          f"peak_bytes_in_use(dev0) {stats.get('peak_bytes_in_use')}",
          flush=True)
    return out


def _check(name, got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    print(f"  {name}: max |kernel - ref| {err:.3e} (tol {tol} abs+rel)")
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                               err_msg=name)


def kernels_phase():
    """paged_attention and flash_attention compiled for the chip at
    qwen3's widths (Dh=128, KVH=8, G=2), each against its ref.py."""
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_ref)
    rng = np.random.default_rng(0)
    B, KVH, G, Dh, ps, n_slots = 8, 8, 2, 128, 16, 64
    n_pages = B * n_slots + 1

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q = rnd(B, KVH * G, Dh)
    kp, vp = rnd(n_pages, ps, KVH, Dh), rnd(n_pages, ps, KVH, Dh)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(B, n_slots), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_slots * ps + 1, size=B),
                          jnp.int32)
    args = (q, kp, vp, tables, lengths)
    got = jax.block_until_ready(
        jax.jit(paged_attention).lower(*args).compile()(*args))
    _check("paged_attention", got, paged_attention_ref(*args), KERNEL_TOL)

    S, H = 512, KVH * G
    q, k, v = rnd(1, S, H, Dh), rnd(1, S, KVH, Dh), rnd(1, S, KVH, Dh)
    got = jax.block_until_ready(
        jax.jit(fa.attention).lower(q, k, v).compile()(q, k, v))

    def heads_major(x):                  # (1, S, h, D) -> (h, S, D)
        return x[0].transpose(1, 0, 2)
    want = attention_ref(heads_major(q), heads_major(k), heads_major(v))
    _check("flash_attention", heads_major(got), want, KERNEL_TOL)


def first_divergence(model, params, prompt, want, got):
    """None when ``got == want``; else (position, the greedy oracle's
    top-2 logit gap there, its top logit), with the oracle's logits
    taken by feeding it ``want`` up to that position."""
    from repro.serve.step import make_prefill_step
    want, got = np.asarray(want), np.asarray(got)
    diff = np.flatnonzero(want != got)
    if diff.size == 0:
        return None
    pos = int(diff[0])
    prefill = jax.jit(make_prefill_step(model, max_len=len(prompt) + pos + 1))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    for t in want[:pos]:
        logits, cache = decode(params, cache,
                               jnp.asarray([[t]], jnp.int32))
    top2 = np.sort(np.asarray(logits[0], np.float32))[-2:]
    return pos, float(top2[1] - top2[0]), float(top2[1])


def compare(label, model, params, prompt, want, got) -> None:
    """Token equality, or a divergence admitted only at a near-tie."""
    d = first_divergence(model, params, prompt, want, got)
    if d is None:
        print(f"  {label}: {len(got)} tokens, equal")
        return
    pos, gap, top = d
    bound = GAP_TOL * abs(top)
    print(f"  {label}: first divergence at token {pos}; oracle top-2 "
          f"gap {gap:.4g} (top logit {top:.4g}, tie bound {bound:.4g})")
    if gap >= bound:
        raise AssertionError(f"{label}: diverged at token {pos} where the "
                             f"oracle is not near a tie")


def check_streams(reqs, gen, vocab) -> None:
    for r in reqs:
        toks = np.asarray(r.generated)
        if len(toks) != gen or toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {r.rid}: {len(toks)} tokens, "
                                 f"range [{toks.min()}, {toks.max()}]")


def serve_phase(cfg, *, n_requests=16, prompt_len=256, gen=64, batch=8,
                n_pages=2048, n_oracle=2, params_seed=0):
    """The CLI's own path (serve_trace, realtime) at ``cfg``'s widths
    with the engine defaults: fused step, speculation k=4, prefix
    sharing.  Every request must return ``gen`` in-vocabulary tokens,
    and ``n_oracle`` of them must match greedy_generate."""
    from repro.launch.serve import serve_trace, synth_requests
    from repro.models import build_model
    from repro.serve import ServeOptions, greedy_generate
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(params_seed))
    reqs = synth_requests(cfg, n_requests, prompt_len, gen, rate=50.0)
    opts = ServeOptions(batch=batch, page_size=16,
                        n_pages=n_pages).sized_for(reqs)
    stats = serve_trace(opts, model, params, reqs, realtime=True)
    print(f"  {len(reqs)} requests x ({prompt_len}+{gen}) tokens, "
          f"batch {batch}, {n_pages} pages: {stats['tokens']} tokens, "
          f"{stats['total_dispatches']} dispatches "
          f"({stats['fused_dispatches']} fused), "
          f"{stats['spec_rounds']} verify rounds, accept rate "
          f"{stats['accept_rate']:.2f}")
    check_streams(reqs, gen, cfg.vocab_size)
    for r in reqs[:n_oracle]:
        want = np.asarray(greedy_generate(
            model, params, {"tokens": jnp.asarray(r.prompt)[None]}, gen,
            cache_len=len(r.prompt) + gen))[0]
        compare(f"request {r.rid} vs greedy_generate", model, params,
                r.prompt, want, r.generated)


def four_chip_phase(cfg, *, n_requests=8, prompt_len=64, gen=16,
                    n_pages=512):
    """TP=4 against tp=1 on the same requests, then four router
    replicas, each with its params and pages on its own chip."""
    from repro.launch.serve import serve_trace, synth_requests
    from repro.models import build_model
    from repro.serve import ServeOptions
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def trace():
        return synth_requests(cfg, n_requests, prompt_len, gen, rate=50.0,
                              seed=1)
    base = ServeOptions(batch=8, page_size=16, n_pages=n_pages)
    base = base.sized_for(trace())
    # one context bucket: one compile per program and device
    base = dataclasses.replace(base, bucket_edges=[base.max_pages_per_seq])

    def run(opts):
        reqs = trace()
        serve_trace(opts, model, params, reqs, realtime=True)
        check_streams(reqs, gen, cfg.vocab_size)
        return reqs

    ref = phase("tp=1", run, base)
    tp4 = phase("tp=4", run, dataclasses.replace(base, tp=4))
    for a, b in zip(ref, tp4):
        compare(f"request {a.rid} tp=4 vs tp=1", model, params, a.prompt,
                a.generated, b.generated)

    def replicas():
        opts = dataclasses.replace(base, replicas=4,
                                   router_policy="round-robin")
        backend = opts.build(model, params)
        reqs = trace()
        backend.run(reqs, realtime=True)
        check_streams(reqs, gen, cfg.vocab_size)
        placed = []
        for i, eng in enumerate(backend.replicas):
            pages = {d.id for d in eng.cache.k_pages.devices()}
            weights = {d.id for leaf in jax.tree.leaves(eng.params)
                       for d in leaf.devices()}
            print(f"  replica {i}: pages on {sorted(pages)}, params on "
                  f"{sorted(weights)}, served "
                  f"{backend.n_dispatched[i]} requests")
            if len(pages) != 1 or pages != weights:
                raise AssertionError(f"replica {i} is not on one chip")
            placed.append(pages.pop())
        if len(set(placed)) != 4:
            raise AssertionError(f"replicas share chips: {placed}")
        return reqs
    rep = phase("replicas=4", replicas)
    for a, b in zip(ref, rep):
        compare(f"request {a.rid} replicas=4 vs tp=1", model, params,
                a.prompt, a.generated, b.generated)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 and four-replica phases")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.compile_cache import use_compile_cache
    print(f"device_kind {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {use_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cfg = configs.get(ARCH)
    if args.four_chips:
        if len(jax.devices()) < 4:
            print("chip_smoke: --four-chips needs 4 chips", file=sys.stderr)
            return 1
        four_chip_phase(cfg)
    else:
        phase("kernels", kernels_phase)
        phase("serve", serve_phase, cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
