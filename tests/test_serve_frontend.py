"""Async streaming front-end: stream-vs-batch token parity on the real
engine (sharing/spec/preemption/cancel), and scheduling policy (WFQ
weights, rate limits, SLO preemption) on a model-free fake backend."""
import asyncio

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.serve import (
    Request, ServeBackend, ServeFrontend, ServeOptions, StreamEvent,
    TenantPolicy, greedy_generate,
)


@pytest.fixture(scope="module")
def qwen3():
    cfg = configs.get_smoke("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n=6, plen=20, shared=0, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, shared, dtype=np.int32)
    return [np.concatenate([prefix,
                            rng.integers(0, cfg.vocab_size, plen,
                                         dtype=np.int32)])
            for _ in range(n)]


def _oracle(model, params, prompts, gen):
    out = []
    for p in prompts:
        toks = greedy_generate(model, params, {"tokens": p[None]}, gen,
                               cache_len=len(p) + gen)
        out.append([int(t) for t in np.asarray(toks)[0]])
    return out


def _backend(model, params, **kw):
    reqs = [Request(rid=0, prompt=np.zeros(64, np.int32),
                    max_new_tokens=16)]
    opts = ServeOptions(batch=kw.pop("batch", 3), page_size=8,
                        chunk_size=16, **kw)
    return opts.sized_for(reqs).build(model, params)


# ----------------------------------------------------- fake backend
class FakeBackend:
    """Deterministic ServeBackend stand-in: each step confirms one
    token (rid*1000 + index) per dispatched request.  Lets the
    scheduling-policy tests run without a model."""

    def __init__(self, capacity=1):
        self._capacity = capacity
        self.active = {}
        self.events = []
        self.dispatch_order = []

    @property
    def capacity(self):
        return self._capacity

    @property
    def n_inflight(self):
        return len(self.active)

    def check_admissible(self, req):
        pass

    def submit(self, req):
        assert len(self.active) < self._capacity, "frontend over-dispatched"
        self.active[req.rid] = req
        self.dispatch_order.append(req.rid)

    def step(self, now=float("inf"), clock=None):
        for rid, req in list(self.active.items()):
            req.generated.append(rid * 1000 + len(req.generated))
            done = len(req.generated) >= req.max_new_tokens
            if done:
                req.finish_time = now
                del self.active[rid]
            self.events.append(StreamEvent(rid=rid,
                                           tokens=(req.generated[-1],),
                                           finished=done))
        return bool(self.active)

    def drain_events(self):
        ev, self.events = self.events, []
        return ev

    def extract(self, rid):
        return self.active.pop(rid, None)

    def cancel(self, rid):
        return self.extract(rid) is not None

    def run(self, requests, *, realtime=False):
        raise NotImplementedError

    def stats(self):
        return {}


def test_fake_backend_satisfies_protocol():
    assert isinstance(FakeBackend(), ServeBackend)


# ------------------------------------------------------------ parity
def test_stream_matches_batch_run(qwen3):
    """Streamed tokens are bitwise-equal to the offline ServeEngine.run
    path and the greedy oracle, with prefix sharing AND speculation on
    (tokens arrive in bursts; content is unchanged)."""
    cfg, model, params = qwen3
    prompts = _prompts(cfg, shared=16)
    gen = 8
    want = _oracle(model, params, prompts, gen)

    eng = _backend(model, params, spec_k=3)
    done = eng.run([Request(rid=i, prompt=p, max_new_tokens=gen)
                    for i, p in enumerate(prompts)], realtime=False)
    assert sorted((r.rid, tuple(r.generated)) for r in done) \
        == [(i, tuple(t)) for i, t in enumerate(want)]

    fe = ServeFrontend(_backend(model, params, spec_k=3))
    streams = [fe.submit(p, gen) for p in prompts]
    for s, toks in zip(streams, want):
        assert list(s) == toks
    st = fe.stats()
    assert st["n_completed"] == len(prompts) and st["n_inflight"] == 0


def test_cancel_mid_stream_and_resubmit_reuses_trie(qwen3):
    """cancel() mid-flight ends the stream; already-yielded tokens
    were confirmed (valid prefix of the oracle); resubmitting streams
    the full oracle answer and re-shares the cancelled request's
    prompt pages from the prefix trie."""
    cfg, model, params = qwen3
    prompts = _prompts(cfg, n=2)
    gen = 8
    want = _oracle(model, params, prompts, gen)
    eng = _backend(model, params)
    fe = ServeFrontend(eng)
    s0, s1 = (fe.submit(p, gen) for p in prompts)
    it = iter(s0)
    head = [next(it) for _ in range(3)]
    assert head == want[0][:3]
    shared_before = eng.cache.n_shared_tokens
    assert s0.cancel()
    assert not s0.cancel()                     # idempotent
    with pytest.raises(StopIteration):
        next(it)
    assert list(s1) == want[1]                 # unaffected neighbor
    s0b = fe.submit(prompts[0], gen)
    assert list(s0b) == want[0]
    # the resubmitted prompt re-shared pages the first attempt donated
    assert eng.cache.n_shared_tokens > shared_before
    assert fe.stats()["n_cancelled"] == 1


def test_cancel_while_queued():
    """Cancelling a not-yet-dispatched stream removes it before it
    ever reaches the backend."""
    be = FakeBackend(capacity=1)
    fe = ServeFrontend(be)
    s0 = fe.submit([1, 2], 3)
    s1 = fe.submit([3, 4], 3)
    assert s1.cancel()
    list(s0)
    assert not fe.busy and be.dispatch_order == [s0.rid]
    assert s1.cancelled and list(s1) == []


def test_async_consumption(qwen3):
    cfg, model, params = qwen3
    prompts = _prompts(cfg, n=3)
    gen = 6
    want = _oracle(model, params, prompts, gen)

    async def go():
        fe = ServeFrontend(_backend(model, params))
        task = asyncio.create_task(fe.serve())

        async def consume(p):
            return [t async for t in fe.submit(p, gen)]

        outs = await asyncio.gather(*(consume(p) for p in prompts))
        fe.close()
        await task
        return outs

    assert asyncio.run(go()) == want


# ------------------------------------------------------------- policy
def test_wfq_weighted_share():
    """Equal-cost backlogs from two tenants dispatch ~proportionally
    to their weights (stride scheduling, capacity-1 backend)."""
    be = FakeBackend(capacity=1)
    fe = ServeFrontend(be, tenants={"gold": TenantPolicy(weight=3.0),
                                    "free": TenantPolicy(weight=1.0)})
    streams = [fe.submit([1, 2, 3, 4], 2, tenant=t)
               for t in ("gold", "free") for _ in range(12)]
    fe.drain()
    assert all(s.finished for s in streams)
    first16 = be.dispatch_order[:16]
    # rids 0..11 are gold, 12..23 free
    gold = sum(1 for rid in first16 if rid < 12)
    assert 10 <= gold <= 13, first16    # ~12/16 = weight 3 of 4


def test_wfq_idle_tenant_earns_no_credit():
    """A tenant that sat idle while another streamed does not get an
    unbounded catch-up burst: it re-joins at the current virtual clock
    and shares from there on."""
    be = FakeBackend(capacity=1)
    fe = ServeFrontend(be, tenants={"a": TenantPolicy(),
                                    "b": TenantPolicy()})
    for _ in range(6):
        fe.submit([1, 2], 2, tenant="a")
    for _ in range(4):                   # a streams alone for a while
        fe.pump()
    for _ in range(6):
        fe.submit([1, 2], 2, tenant="b")
    fe.drain()
    tail = be.dispatch_order[-8:]
    a_tail = sum(1 for rid in tail if rid < 6)
    assert 2 <= a_tail <= 6, be.dispatch_order   # interleaved, no b-burst


def test_rate_limit_throttles_sustained_load():
    """A rate-limited tenant overdraws once, then waits out its debt:
    admissions are spaced by cost/rate in clock units, while an
    unlimited tenant proceeds freely."""
    be = FakeBackend(capacity=2)
    fe = ServeFrontend(be, tenants={
        "lim": TenantPolicy(rate=1.0),    # 1 cost unit per step
        "unl": TenantPolicy()})
    cost = 4 + 2                          # prompt 4 + gen 2
    lim = [fe.submit([1, 2, 3, 4], 2, tenant="lim") for _ in range(3)]
    unl = [fe.submit([1, 2, 3, 4], 2, tenant="unl") for _ in range(3)]
    t_lim, t_unl = [], []
    step = 0
    while fe.busy:
        step += 1
        n_before = len(be.dispatch_order)
        fe.pump(now=float(step))
        for rid in be.dispatch_order[n_before:]:
            (t_lim if any(s.rid == rid for s in lim)
             else t_unl).append(step)
    assert all(s.finished for s in lim + unl)
    # unlimited tenant admitted as fast as capacity allowed
    assert t_unl[-1] - t_unl[0] <= 4
    # limited tenant: successive admissions spaced by ~cost/rate (the
    # initial burst credit — one clock unit's worth — shaves at most
    # burst/rate off the first gap)
    gaps = [b - a for a, b in zip(t_lim, t_lim[1:])]
    assert all(g >= cost - 1 for g in gaps), (t_lim, gaps)


def test_slo_interactive_preempts_batch():
    """With every slot full of batch work, an interactive arrival
    preempts the cheapest-to-replay victim, which later resumes and
    still finishes; slo_aware=False leaves batch work alone."""
    for aware, expect_preempt in ((True, 1), (False, 0)):
        be = FakeBackend(capacity=2)
        fe = ServeFrontend(be, slo_aware=aware)
        batch = [fe.submit([1, 2], 8) for _ in range(2)]
        fe.pump()                         # both dispatched, 1 token each
        inter = fe.submit([3, 4], 2, slo_class="interactive")
        fe.drain()
        assert fe.stats()["n_slo_preemptions"] == expect_preempt
        assert all(s.finished for s in batch + [inter])
        if aware:
            # victim kept its confirmed tokens and finished its budget
            victim = min(batch, key=lambda s: s.rid)
            assert len(victim.req.generated) == 8
            assert victim.req.n_preemptions == 1
            # interactive finished before the preempted victim resumed
            # its last token
            assert inter.req.finish_time <= victim.req.finish_time


def test_slo_preemption_parity_on_engine(qwen3):
    """SLO preemption on the real engine: the preempted batch request
    replays and still matches the oracle bitwise."""
    cfg, model, params = qwen3
    prompts = _prompts(cfg, n=3)
    gen = 16
    want = _oracle(model, params, prompts, gen)
    fe = ServeFrontend(_backend(model, params, batch=2))
    b0 = fe.submit(prompts[0], gen)
    b1 = fe.submit(prompts[1], gen)
    for _ in range(3):                    # let batch work get going
        fe.pump()
    hi = fe.submit(prompts[2], gen, slo_class="interactive")
    fe.drain()
    assert fe.stats()["n_slo_preemptions"] >= 1
    assert [list(b0), list(b1), list(hi)] == want


def test_submit_rejects_inadmissible(qwen3):
    cfg, model, params = qwen3
    fe = ServeFrontend(_backend(model, params))
    with pytest.raises(ValueError):
        fe.submit(np.zeros(100000, np.int32), 4)      # never fits
    with pytest.raises(ValueError):
        fe.submit([1, 2], 4, slo_class="platinum")    # unknown class
    with pytest.raises(ValueError):
        fe.submit([1, 2], 4, rid=fe.submit([3, 4], 2).rid)
