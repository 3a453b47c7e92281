"""Tensor-parallel serving: token streams from a sharded engine must be
bit-identical to the single-device engine across every serve feature
(chunked prefill, prefix sharing/COW, speculative decode,
preemption/replay).

Sharded runs need >1 device while the rest of the suite must see
exactly one, so (like test_multidevice.py) each scenario runs in a
subprocess with its own forced-host-device XLA_FLAGS.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_devices(n: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=1800)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_tp2_token_parity_sharing_and_spec():
    """tp=2 vs single device on one trace exercising chunked prefill,
    prefix sharing with mid-page COW divergence, and speculative
    decode — streams must match bit for bit, and the page arrays must
    actually be sharded across devices."""
    print(run_devices(8, """
        import jax, numpy as np
        from repro import configs
        from repro.models import build_model
        from repro.serve import Request, ServeEngine

        cfg = configs.get_smoke("qwen3-0.6b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, cfg.vocab_size, size=(20,)).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.integers(0, cfg.vocab_size,
                                                size=(7,)).astype(np.int32)])
                   for _ in range(3)]
        # a long unshared prompt spanning several chunks rides along
        prompts.append(rng.integers(0, cfg.vocab_size,
                                    size=(40,)).astype(np.int32))

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]

        kw = dict(max_batch=2, n_pages=40, page_size=8,
                  max_pages_per_seq=8, chunk_size=16, spec_k=4)
        ref = ServeEngine(model, params, **kw)
        want = {r.rid: list(r.generated) for r in ref.run(trace())}
        tp = ServeEngine(model, params, tp=2, **kw)
        assert len(tp.cache.k_pages.sharding.device_set) == 2, \\
            tp.cache.k_pages.sharding
        got = {r.rid: list(r.generated) for r in tp.run(trace())}
        assert want == got, (want, got)
        assert tp.cache.n_cow >= 2 and tp.n_spec_rounds >= 1
        tp.cache.check_invariants()
        print("tp2 sharing+spec parity ok", tp.n_spec_rounds)
    """))


def test_tp2_batched_prefill_parity():
    """Batched chunked prefill (prefill_batch > 1) composed with
    tensor parallelism: a tp=2 engine co-ingesting a burst must stream
    bit-identically to the single-device *serialized* engine — the
    per-row tables/starts/valids are replicated control metadata, the
    gathered context and page scatter shard on KV heads."""
    print(run_devices(8, """
        import jax, numpy as np
        from repro import configs
        from repro.models import build_model
        from repro.serve import Request, ServeEngine

        cfg = configs.get_smoke("qwen3-0.6b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, cfg.vocab_size, size=(20,)).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.integers(0, cfg.vocab_size,
                                                size=(7,)).astype(np.int32)])
                   for _ in range(3)]
        # ragged unshared prompts straddling chunk boundaries ride along
        prompts += [rng.integers(0, cfg.vocab_size,
                                 size=(L,)).astype(np.int32)
                    for L in (15, 33)]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]

        kw = dict(max_batch=4, n_pages=64, page_size=8,
                  max_pages_per_seq=8, chunk_size=16)
        ref = ServeEngine(model, params, prefill_batch=1, **kw)
        want = {r.rid: list(r.generated) for r in ref.run(trace())}
        tp = ServeEngine(model, params, tp=2, prefill_batch=4, **kw)
        got = {r.rid: list(r.generated) for r in tp.run(trace())}
        assert want == got, (want, got)
        assert tp.n_prefill_dispatches < tp.n_prefill_chunks, \\
            "burst was meant to co-ingest"
        assert tp.cache.n_shared_tokens >= 2 * 20, \\
            "in-burst sharing must fire under tp too"
        tp.cache.check_invariants()
        print("tp2 batched-prefill parity ok",
              tp.n_prefill_dispatches, tp.n_prefill_chunks)
    """))


def test_tp2_preemption_replay_parity():
    """Page pressure forces eviction + recompute-replay on the sharded
    engine; the replayed stream still matches the single-device one."""
    print(run_devices(8, """
        import jax, numpy as np
        from repro import configs
        from repro.models import build_model
        from repro.serve import Request, ServeEngine

        cfg = configs.get_smoke("qwen3-0.6b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        lens, gen = [30, 28, 18], 8
        prompts = [rng.integers(0, cfg.vocab_size,
                                size=(L,)).astype(np.int32) for L in lens]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)]

        kw = dict(max_batch=3, n_pages=13, page_size=8,
                  max_pages_per_seq=8, prefix_sharing=False)
        ref = ServeEngine(model, params, **kw)
        want = {r.rid: list(r.generated) for r in ref.run(trace())}
        tp = ServeEngine(model, params, tp=2, **kw)
        got = {r.rid: list(r.generated) for r in tp.run(trace())}
        assert tp.n_replay_steps >= 1, "trace was sized to force replay"
        assert want == got, (want, got)
        tp.cache.check_invariants()
        print("tp2 preemption parity ok", tp.n_replay_steps)
    """))


def test_tp4_token_parity():
    """tp=4 on a 4-KV-head config (the smoke qwen3 has only 2 KV
    heads); also checks the TP engine composes with an explicit
    ServePrograms-style shared bundle across two replicas."""
    print(run_devices(8, """
        import jax, numpy as np
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.serve import Request, ServeEngine
        from repro.serve.parallel import TPServePrograms

        cfg = ModelConfig(name="tp4-test", family="dense", n_layers=2,
                          d_model=64, n_heads=8, n_kv_heads=4,
                          head_dim=16, d_ff=128, vocab_size=256,
                          qk_norm=True, tie_embeddings=True,
                          attn_kv_chunk=16)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 256, size=(L,)).astype(np.int32)
                   for L in (9, 21, 14)]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]

        kw = dict(max_batch=2, n_pages=24, page_size=8,
                  max_pages_per_seq=8)
        ref = ServeEngine(model, params, **kw)
        want = {r.rid: list(r.generated) for r in ref.run(trace())}
        progs = TPServePrograms(model, tp=4)
        a = ServeEngine(model, params, programs=progs, **kw)
        b = ServeEngine(model, params, programs=progs, **kw)
        got_a = {r.rid: list(r.generated) for r in a.run(trace())}
        got_b = {r.rid: list(r.generated) for r in b.run(trace())}
        assert want == got_a == got_b, (want, got_a, got_b)
        print("tp4 parity ok (shared programs)")
    """))


def test_tp2_parity_bias_gelu_untied_family():
    """The other sharded param shapes: qkv biases (sharded with their
    heads), gelu w1/b1 (sharded hidden), layernorm, and an untied
    unembedding head (replicated) — still bitwise, spec on."""
    print(run_devices(8, """
        import jax, numpy as np
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        from repro.serve import Request, ServeEngine

        cfg = ModelConfig(name="tp-bias-test", family="dense",
                          n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=256, qkv_bias=True,
                          mlp_kind="gelu", norm_kind="layernorm",
                          tie_embeddings=False, attn_kv_chunk=16)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(3))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 256, size=(L,)).astype(np.int32)
                   for L in (9, 21, 14)]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]

        kw = dict(max_batch=2, n_pages=24, page_size=8,
                  max_pages_per_seq=8, spec_k=3)
        want = {r.rid: list(r.generated)
                for r in ServeEngine(model, params, **kw).run(trace())}
        got = {r.rid: list(r.generated)
               for r in ServeEngine(model, params, tp=2,
                                    **kw).run(trace())}
        assert want == got, (want, got)
        print("bias/gelu/untied tp2 parity ok")
    """))


def test_tp_validation_rejects_bad_configs():
    """Divisibility and family checks fail fast, without any mesh."""
    from repro import configs
    from repro.models import build_model
    from repro.serve.parallel import validate_tp

    model = build_model(configs.get_smoke("qwen3-0.6b"))
    validate_tp(model, 2)                     # 4 heads / 2 kv heads
    with pytest.raises(ValueError, match="does not divide"):
        validate_tp(model, 4)                 # kv heads indivisible
    moe = build_model(configs.get_smoke("deepseek-moe-16b"))
    with pytest.raises(ValueError):
        validate_tp(moe, 2)


def test_router_replicas_placed_on_their_own_devices():
    """A tp=1 fleet from ServeOptions puts replica i's params and page
    pool on device i (the pool allocated there, not moved), and each
    replica's streams equal a lone engine's."""
    print(run_devices(4, """
        import dataclasses
        import jax, numpy as np
        from repro import configs
        from repro.models import build_model
        from repro.serve import Request, ServeOptions

        cfg = configs.get_smoke("qwen3-0.6b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, cfg.vocab_size, size=(12,))
                   .astype(np.int32) for _ in range(8)]

        def trace():
            return [Request(rid=i, prompt=p, max_new_tokens=5)
                    for i, p in enumerate(prompts)]

        opts = ServeOptions(batch=2, page_size=8,
                            router_policy="round-robin").sized_for(trace())
        want = {r.rid: list(r.generated)
                for r in opts.build(model, params).run(trace())}
        fleet = dataclasses.replace(opts, replicas=4).build(model, params)
        got = {r.rid: list(r.generated) for r in fleet.run(trace())}
        assert want == got, (want, got)
        devs = jax.devices()
        for i, eng in enumerate(fleet.replicas):
            for arr in (eng.cache.k_pages, eng.cache.v_pages,
                        *jax.tree.leaves(eng.params)):
                assert arr.devices() == {devs[i]}, (i, arr.devices())
        assert all(n == 2 for n in fleet.n_dispatched), fleet.n_dispatched
        print("replica placement ok")
    """))
