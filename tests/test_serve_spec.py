"""Speculative decoding on the paged serve engine: bitwise equivalence
of the multi-token verify program against sequential decode, token-exact
parity of the speculative engine vs ``greedy_generate`` under every
PR 2 composition (chunked prefill, prefix sharing/COW, preemption), and
allocator invariants under random speculative accept/reject churn."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.models import build_model
from repro.serve import (DraftModelDrafter, PagedKVCache,
                         PromptLookupDrafter, Request, ServeEngine,
                         greedy_generate)


@pytest.fixture(scope="module")
def qwen3():
    cfg = configs.get_smoke("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def oracles(model, params, prompts, gen):
    return {i: np.asarray(
        greedy_generate(model, params, {"tokens": p[None]}, gen,
                        cache_len=len(p) + gen))[0]
        for i, p in enumerate(prompts)}


def assert_parity(done, oracle):
    for r in done:
        np.testing.assert_array_equal(
            np.asarray(r.generated, np.int32), oracle[r.rid],
            err_msg=f"request {r.rid} diverged")


# --------------------------------------------------------- verify step
def test_verify_step_bitwise_matches_sequential_decode(qwen3):
    """One verify call over T tokens returns logits AND page contents
    bit-identical to T sequential decode_step_paged calls — the whole
    speculation parity guarantee reduces to this equivalence."""
    cfg, model, params = qwen3
    B, ps, n_pages, npps, T = 3, 8, 32, 6, 5
    rng = np.random.default_rng(0)
    shape = (cfg.n_layers, n_pages, ps, cfg.n_kv_heads, cfg.head_dim)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    tables = np.zeros((B, npps), np.int32)
    tables[:, :4] = rng.permutation(np.arange(1, n_pages))[:B * 4] \
        .reshape(B, 4)
    lengths = np.asarray([9, 17, 3], np.int32)     # ragged positions
    toks = rng.integers(0, cfg.vocab_size, size=(B, T)).astype(np.int32)

    st_ = {"k_pages": k_pages, "v_pages": v_pages,
           "page_tables": jnp.asarray(tables),
           "lengths": jnp.asarray(lengths)}
    seq = []
    decode = jax.jit(model.decode_step_paged)
    for t in range(T):
        lg, st_ = decode(params, st_, jnp.asarray(toks[:, t:t + 1]))
        seq.append(np.asarray(lg))
    seq = np.stack(seq, axis=1)                    # (B, T, V)

    st2 = {"k_pages": k_pages, "v_pages": v_pages,
           "page_tables": jnp.asarray(tables),
           "lengths": jnp.asarray(lengths)}
    ver, st2 = jax.jit(model.verify_step_paged)(params, st2,
                                                jnp.asarray(toks))
    np.testing.assert_array_equal(seq, np.asarray(ver))
    np.testing.assert_array_equal(
        np.asarray(st_["k_pages"], np.float32),
        np.asarray(st2["k_pages"], np.float32))
    np.testing.assert_array_equal(
        np.asarray(st_["v_pages"], np.float32),
        np.asarray(st2["v_pages"], np.float32))


def test_verify_window_crossing_the_table_end_matches_decode(qwen3):
    """Row 0's T-token window runs past the end of its gathered table
    (lengths + T > n * ps): the positions it can confirm give the logits
    sequential decode steps give, and the pages end up the same — the
    overlay drops the lanes past the buffer and the writer routes them
    to the null page; neither clamps them onto the row's last page."""
    cfg, model, params = qwen3
    B, ps, n, T = 2, 8, 3, 5
    specs = model.paged_state_specs(B, n_pages=16, page_size=ps,
                                    max_pages_per_seq=n)
    rng = np.random.default_rng(1)
    shape = specs["k_pages"].shape
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    tables = jnp.asarray([[3, 7, 11], [2, 5, 9]], jnp.int32)
    lengths = np.asarray([n * ps - 3, 6], np.int32)   # row 0: 21 + 5 > 24
    toks = rng.integers(0, cfg.vocab_size, size=(B, T)).astype(np.int32)

    st_ = {"k_pages": k_pages, "v_pages": v_pages, "page_tables": tables,
           "lengths": jnp.asarray(lengths)}
    seq = []
    decode = jax.jit(model.decode_step_paged)
    for t in range(T):
        lg, st_ = decode(params, st_, jnp.asarray(toks[:, t:t + 1]))
        seq.append(np.asarray(lg))
    seq = np.stack(seq, axis=1)                    # (B, T, V)

    st2 = {"k_pages": k_pages, "v_pages": v_pages, "page_tables": tables,
           "lengths": jnp.asarray(lengths)}
    ver, st2 = jax.jit(model.verify_step_paged)(params, st2,
                                                jnp.asarray(toks))
    ver = np.asarray(ver)
    for b in range(B):
        ok = min(T, n * ps - lengths[b])           # confirmable positions
        np.testing.assert_array_equal(seq[b, :ok], ver[b, :ok])
    assert min(T, n * ps - lengths[0]) < T         # the window crosses
    for name in ("k_pages", "v_pages"):
        np.testing.assert_array_equal(np.asarray(st_[name], np.float32),
                                      np.asarray(st2[name], np.float32))
    # row 0's last page keeps its history: nothing clamped onto it
    last = np.asarray(k_pages[:, 11], np.float32)
    np.testing.assert_array_equal(
        np.asarray(st2["k_pages"][:, 11], np.float32)[:, :lengths[0] % ps],
        last[:, :lengths[0] % ps])


# ------------------------------------------------------- engine parity
def test_spec_engine_token_exact_vs_greedy_generate(qwen3):
    """Speculation on, more requests than slots, ragged prompts: every
    stream matches the sequential oracle token for token, and every
    page returns to the free list."""
    cfg, model, params = qwen3
    rng = np.random.default_rng(7)
    lens, gen = [9, 17, 24, 12, 31, 8], 10
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in lens]
    oracle = oracles(model, params, prompts, gen)
    eng = ServeEngine(model, params, max_batch=3, n_pages=24,
                      page_size=8, max_pages_per_seq=8, spec_k=4)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert len(done) == len(prompts)
    assert_parity(done, oracle)
    assert eng.n_spec_rounds > 0 and eng.n_drafted > 0
    eng.cache.check_invariants()
    eng.cache.release_prefix_pages(len(eng.cache.prefix))
    eng.cache.check_invariants()
    assert eng.cache.free_pages == 23

    # a second, repeated workload warms the cross-request n-gram index:
    # acceptance must rise while the streams stay bit-identical
    drafted0, acc0 = eng.n_drafted, eng.n_draft_accepted
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert_parity(done, oracle)
    warm_rate = (eng.n_draft_accepted - acc0) / (eng.n_drafted - drafted0)
    assert warm_rate > 0.5, f"warm accept rate {warm_rate:.2f}"


def test_spec_engine_preemption_token_exact(qwen3):
    """Page pressure forces preemption mid-speculation; the evicted
    request recomputes (replay) and still matches the oracle."""
    cfg, model, params = qwen3
    rng = np.random.default_rng(11)
    lens, gen = [16, 16, 11], 8
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in lens]
    oracle = oracles(model, params, prompts, gen)
    # The pool forces a preemption whatever the weights draw: 5 usable
    # pages of 8.  Both 16-token prompts are admitted and co-ingested
    # in the first step (2 pages each + 1 spare page kept free at
    # admission), so 1 page is left.  The first write of each request
    # lands at position 16, on a third page: the older takes the last
    # free page, the younger is evicted, however many drafts verify.
    eng = ServeEngine(model, params, max_batch=2, prefill_batch=2,
                      n_pages=6, page_size=8, max_pages_per_seq=8,
                      prefix_sharing=False, spec_k=4)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert len(done) == len(prompts)
    assert sum(r.n_preemptions for r in done) >= 1
    assert_parity(done, oracle)
    eng.cache.check_invariants()


def test_spec_engine_sharing_chunking_token_exact(qwen3):
    """The full composition: chunked prefill + COW prefix sharing +
    speculation, with prompts diverging mid-page."""
    cfg, model, params = qwen3
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab_size, size=(20,)).astype(np.int32)
    gen = 6
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size,
                                            size=(7,)).astype(np.int32)])
               for _ in range(3)]
    oracle = oracles(model, params, prompts, gen)
    eng = ServeEngine(model, params, max_batch=2, n_pages=32,
                      page_size=8, max_pages_per_seq=8, chunk_size=16,
                      spec_k=4)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert eng.cache.n_shared_tokens >= 2 * 20
    assert eng.cache.n_cow >= 2
    assert_parity(done, oracle)
    eng.cache.check_invariants()


def test_spec_engine_eos_stops_at_first_occurrence(qwen3):
    """A verify round can bank several tokens at once; anything banked
    after the first eos must be discarded (the oracle stops there)."""
    cfg, model, params = qwen3
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(22,)).astype(np.int32)
    gen = 10
    oracle = oracles(model, params, [prompt], gen)[0]
    eos = int(oracle[4])
    stop = int(np.nonzero(oracle == eos)[0][0])    # first occurrence
    eng = ServeEngine(model, params, max_batch=2, n_pages=16,
                      page_size=8, max_pages_per_seq=8, spec_k=4,
                      eos_id=eos)
    done = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=gen)])
    np.testing.assert_array_equal(
        np.asarray(done[0].generated, np.int32), oracle[:stop + 1])
    eng.cache.check_invariants()


def test_draft_model_drafter_rejection_path(qwen3):
    """A random-init draft model proposes garbage: near-total rejection
    must leave streams exact (speculation can only change speed), and
    detach must drop per-slot draft state."""
    cfg, model, params = qwen3
    dcfg = configs.get_smoke("qwen2-0.5b")
    dmodel = build_model(dcfg)
    drafter = DraftModelDrafter(dmodel,
                                dmodel.init(jax.random.PRNGKey(1)),
                                cfg_target=cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in (9, 14)]
    gen = 6
    oracle = oracles(model, params, prompts, gen)
    eng = ServeEngine(model, params, max_batch=2, n_pages=16,
                      page_size=8, max_pages_per_seq=8, spec_k=3,
                      drafter=drafter)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert eng.n_drafted > 0
    assert_parity(done, oracle)
    assert not drafter._slots          # all slots detached at finish
    eng.cache.check_invariants()


def test_draft_model_vocab_mismatch_rejected(qwen3):
    import dataclasses
    cfg, model, _ = qwen3
    bad = dataclasses.replace(configs.get_smoke("stablelm-1.6b"),
                              vocab_size=cfg.vocab_size + 1)
    dmodel = build_model(bad)
    with pytest.raises(ValueError, match="vocab"):
        DraftModelDrafter(dmodel, None, cfg_target=cfg)


# ------------------------------------------------------------- drafter
def test_prompt_lookup_drafter_semantics():
    """Lag-by-one indexing: a trailing plateau finds its own earlier
    occurrence, cross-request reuse works inside one scope, and
    distinct scopes never share n-gram statistics."""
    d = PromptLookupDrafter(max_ngram=3, min_ngram=1, scope_tokens=4)
    ra = Request(rid=0, prompt=np.asarray([1, 2, 3, 4], np.int32),
                 max_new_tokens=32)
    ra.generated = [7, 7, 7]
    # plateau: trailing (7, 7) hits the earlier (7, 7) -> 7 occurrence
    assert d.propose(0, ra, 4) == [7]
    ra.generated = [7, 7, 7, 7]
    # the (7,7,7)->7 entry points at the live frontier: one confirmed
    # continuation token so far (the source list keeps growing)
    assert d.propose(0, ra, 4) == [7]
    # same scope, different request: the motif transfers
    rb = Request(rid=1, prompt=np.asarray([1, 2, 3, 4], np.int32),
                 max_new_tokens=32)
    rb.generated = [7]
    assert d.propose(1, rb, 3) == [7, 7, 7]
    # different scope: isolated index, no draft
    rc = Request(rid=2, prompt=np.asarray([9, 9, 9, 9], np.int32),
                 max_new_tokens=32)
    rc.generated = [7]
    assert d.propose(2, rc, 3) == []
    d.detach(0)
    d.detach(1)
    d.detach(2)
    assert not d._slots


def _scoped_request(rid, scope_token, generated):
    r = Request(rid=rid,
                prompt=np.full((4,), scope_token, np.int32),
                max_new_tokens=64)
    r.generated = list(generated)
    return r


def test_prompt_lookup_index_evicts_lru_scope_only():
    """At the entry budget the index drops whole least-recently-used
    scopes; the scope in use survives (the old wholesale reset cooled
    every workload whenever one overgrew)."""
    d = PromptLookupDrafter(max_ngram=2, min_ngram=1, scope_tokens=4,
                            max_entries=24)
    # three workloads populate three scopes, oldest first
    for i, tok in enumerate((1, 2, 3)):
        d.propose(i, _scoped_request(i, tok, [7, 8, 7, 8, 7]), 0)
    assert len(d._scopes) == 3 and d.n_scope_evictions == 0
    # touch scope 1 so scope 0 is now the stalest
    d.propose(1, _scoped_request(10, 2, [7, 8, 7, 8, 7, 8]), 0)
    # a fourth workload overflows the budget -> scope 0 evicted
    d.propose(3, _scoped_request(3, 4, [7, 8, 7, 8, 7]), 0)
    assert d.n_scope_evictions >= 1
    scopes = set(d._scopes)
    assert (1,) * 4 not in scopes, "evicted the hot scope, not the LRU"
    assert (4,) * 4 in scopes, "the in-use scope must survive"
    # surviving scopes still draft; the evicted one restarts cold
    rb = _scoped_request(20, 2, [7])
    assert d.propose(4, rb, 2) == [8, 7]
    rc = _scoped_request(21, 1, [7])
    assert d.propose(5, rc, 2) == []
    assert d._n_entries == sum(len(ix) for ix in d._scopes.values())


def test_prompt_lookup_single_giant_scope_resets_itself():
    """A single scope exceeding the whole budget resets in place
    instead of looping the LRU forever."""
    d = PromptLookupDrafter(max_ngram=2, min_ngram=1, scope_tokens=4,
                            max_entries=8)
    seq = list(range(40))                 # 40 distinct unigram entries
    d.propose(0, _scoped_request(0, 1, seq), 0)
    assert d.n_scope_evictions >= 1
    assert d._n_entries <= 8
    assert len(d._scopes) == 1           # scope still registered


# -------------------------------------------- allocator spec invariants
def make_cache(model, **kw):
    kw = {"max_batch": 4, "n_pages": 24, "page_size": 8,
          "max_pages_per_seq": 12, **kw}
    return PagedKVCache(model, **kw)


def test_ensure_headroom_multi_token_and_rollback(qwen3):
    """A k+1 write window spanning a page boundary allocates ahead;
    rollback returns exactly the pages past the confirmed frontier."""
    _, model, _ = qwen3
    c = make_cache(model)
    assert c.alloc_slot(0, 14) is not None        # 2 pages
    c.lengths[0] = 14
    free0 = c.free_pages
    # window 14..20 crosses into page 3
    assert c.ensure_headroom(0, 7)
    assert len(c.used_pages(0)) == 3
    assert c.free_pages == free0 - 1
    # nothing accepted: the speculative page comes straight back
    assert c.rollback_spec(0) == 1
    assert c.free_pages == free0
    c.check_invariants()
    # partial acceptance into the new page: it is kept
    assert c.ensure_headroom(0, 7)
    c.lengths[0] = 17
    assert c.rollback_spec(0) == 0
    assert len(c.used_pages(0)) == 3
    c.check_invariants()


def test_rollback_never_touches_shared_prompt_pages(qwen3):
    """Speculative rollback only releases private growth — donated
    (trie-referenced) and reader-shared prompt pages keep their
    refcounts."""
    _, model, _ = qwen3
    c = make_cache(model)
    prompt = np.arange(20, dtype=np.int32)
    assert c.alloc_slot(0, 20, prompt=prompt) == 0
    c.lengths[0] = 20
    c.register_prefix(0, prompt)
    shared = c.alloc_slot(1, 20, prompt=prompt)
    assert shared == 19
    for slot in (0, 1):
        c.lengths[slot] = 20
        assert c.ensure_headroom(slot, 5)          # 20..24 -> page 4
        n = c.rollback_spec(slot)
        assert n == 1
        c.check_invariants()
    # trie + both readers still agree on the shared full pages
    assert c.used_pages(0)[:2] == c.used_pages(1)[:2]


@given(ops=st.lists(st.integers(0, 10 ** 6), min_size=1, max_size=40))
@settings(max_examples=25, deadline=None)
def test_spec_churn_invariants_random(qwen3, ops):
    """Random speculative accept/reject sequences over slots sharing a
    donated prompt: free-list and refcount invariants hold after every
    round, the frontier page is always covered, and draining returns
    every page."""
    _, model, _ = qwen3
    c = make_cache(model)
    prompt = np.arange(12, dtype=np.int32)
    assert c.alloc_slot(0, 12, prompt=prompt) == 0
    c.lengths[0] = 12
    c.register_prefix(0, prompt)
    assert c.alloc_slot(1, 12, prompt=prompt) is not None
    c.lengths[1] = 12
    for v in ops:
        slot = v % 2
        n_draft = (v // 2) % 5
        accepted = (v // 10) % (n_draft + 2)       # 0 .. n_draft+1
        if c.ensure_headroom(slot, n_draft + 1):
            c.lengths[slot] += accepted
        c.rollback_spec(slot)                      # also after failures
        c.check_invariants()
        used = len(c.used_pages(slot))
        assert used <= int(c.lengths[slot]) // c.page_size + 1
        assert used >= c.pages_for(int(c.lengths[slot]))
    c.free_slot(0)
    c.free_slot(1)
    c.release_prefix_pages(len(c.prefix))
    c.check_invariants()
    assert c.free_pages == 23


@given(seed=st.integers(0, 10 ** 6), k=st.integers(1, 6))
@settings(max_examples=4, deadline=None)
def test_spec_engine_random_traces_token_exact(qwen3, seed, k):
    """Property-style end-to-end: random prompts and draft depths stay
    bit-identical to the oracle with sharing + chunking enabled."""
    cfg, model, params = qwen3
    rng = np.random.default_rng(seed)
    lens = rng.choice([6, 10, 19], size=3)
    gen = int(rng.integers(3, 7))
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in lens]
    oracle = oracles(model, params, prompts, gen)
    eng = ServeEngine(model, params, max_batch=2, n_pages=24,
                      page_size=8, max_pages_per_seq=6, chunk_size=8,
                      spec_k=k)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)])
    assert_parity(done, oracle)
    eng.cache.check_invariants()
