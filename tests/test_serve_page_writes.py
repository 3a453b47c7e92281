"""The step programs read the page pools in place and never return them;
one donated writer stores each step's new K/V.

* No output of a public step program, and no ``xs`` or ``ys`` of its
  layer scan, has the shape of a page pool, per layer or stacked.
* ``write_pages`` takes the pools donated (the arrays passed in are
  deleted) and stores exactly what the composed ``*_step_paged`` method
  stores.
* An engine follows every program dispatch with one page write, and
  compiles the writer only while it is built.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.serve import Request, ServeEngine
from repro.serve.step import ServePrograms

B, BP, CHUNK, NB, T_VERIFY = 3, 2, 8, 4, 3
N_PAGES, PAGE, MPPS = 24, 8, 6


@pytest.fixture(scope="module")
def bundle():
    cfg = configs.get_smoke("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, ServePrograms(model)


def _state(model, seed=0):
    """A page state with random pools and ragged rows, all pages their
    own (the null page too is filled: its bits must not matter)."""
    specs = model.paged_state_specs(B, n_pages=N_PAGES, page_size=PAGE,
                                    max_pages_per_seq=MPPS)
    rng = np.random.default_rng(seed)
    shape = specs["k_pages"].shape
    tables = np.zeros((B, MPPS), np.int32)
    tables[:, :4] = 1 + np.arange(B * 4).reshape(B, 4)     # pages 1..12
    return {"k_pages": jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            "v_pages": jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            "page_tables": jnp.asarray(tables),
            "lengths": jnp.asarray([9, 17, 3], jnp.int32)}


def _args(model, kind, state):
    """(the ``kind`` program's arguments after params, the model method
    that composes its compute and write halves)."""
    chunk = (jnp.ones((BP, CHUNK), jnp.int32),
             jnp.asarray(state["page_tables"][:BP, :NB]),
             jnp.asarray([0, 8], jnp.int32), jnp.asarray([8, 5], jnp.int32))
    if kind == "chunk":
        pages = {k: state[k] for k in ("k_pages", "v_pages")}
        return (pages,) + chunk, model.prefill_chunk_paged
    width = {"decode": 1, "verify": T_VERIFY, "fused": T_VERIFY}[kind]
    toks = jnp.arange(B * width, dtype=jnp.int32).reshape(B, width)
    if kind == "fused":
        # prefill rows own pages no decode row reads or writes
        fresh = jnp.asarray([[20, 21, 0, 0], [22, 23, 0, 0]], jnp.int32)
        return ((state, toks) + (chunk[0], fresh) + chunk[2:],
                model.fused_step_paged)
    step = (model.decode_step_paged if kind == "decode"
            else model.verify_step_paged)
    return (state, toks), step


def _scans(jaxpr):
    """Every scan equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


@pytest.mark.parametrize("kind", ["decode", "chunk", "verify", "fused"])
def test_step_programs_never_carry_a_pool(bundle, kind):
    cfg, model, params, programs = bundle
    state = _state(model)
    args, _ = _args(model, kind, state)
    pool = state["k_pages"].shape
    pool_shapes = {pool, pool[1:]}
    closed = jax.make_jaxpr(getattr(programs, kind))(params, *args)
    outs = [v.aval.shape for v in closed.jaxpr.outvars]
    assert not pool_shapes & set(outs), (kind, outs)
    scans = list(_scans(closed.jaxpr))
    assert scans, kind
    for eqn in scans:
        n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
        xs = [v.aval.shape for v in eqn.invars[n_in:]]
        ys = [v.aval.shape for v in
              eqn.outvars[eqn.params["num_carry"]:]]
        assert not pool_shapes & set(xs + ys), (kind, xs, ys)


@pytest.mark.parametrize("kind", ["decode", "chunk", "verify", "fused"])
def test_write_pages_donates_and_stores_the_step(bundle, kind):
    """The public program then the writer leave the pools the composed
    model method leaves; the writer's pool arguments are deleted."""
    cfg, model, params, programs = bundle
    args, composed = _args(model, kind, _state(model))
    _, want = jax.jit(composed)(params, *args)
    _, writes = getattr(programs, kind)(params, *args)
    # the writer consumes its own copies: the program's inputs stay
    # alive (a caller may pass them again)
    pools = [jnp.array(args[0][k]) for k in ("k_pages", "v_pages")]
    got = programs.write_pages(*pools, writes)
    assert all(p.is_deleted() for p in pools)
    assert not any(args[0][k].is_deleted() for k in ("k_pages", "v_pages"))
    for name, g in zip(("k_pages", "v_pages"), got):
        np.testing.assert_array_equal(np.asarray(want[name], np.float32),
                                      np.asarray(g, np.float32))


def test_engine_writes_pages_once_a_dispatch(bundle):
    """Prefill, fused, decode, verify and replay dispatches (a tight
    pool forces preemption) are each followed by one page write, and no
    step compiles the writer again."""
    cfg, model, params, programs = bundle
    rng = np.random.default_rng(3)
    eng = ServeEngine(model, params, max_batch=3, n_pages=14, page_size=8,
                      max_pages_per_seq=8, chunk_size=16, prefill_batch=2,
                      spec_k=2, programs=programs)
    compiled = programs.write_pages._cache_size()
    done = eng.run([Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=(n,)).astype(np.int32), max_new_tokens=14)
        for i, n in enumerate([30, 12, 21, 9, 17])])
    st = eng.stats()
    assert len(done) == 5
    assert st["n_replay_steps"] > 0 and st["n_fused_dispatches"] > 0
    assert st["n_page_writes"] == st["n_total_dispatches"]
    assert not eng.tel.registry.audit()
    assert programs.write_pages._cache_size() == compiled
