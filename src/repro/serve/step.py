"""Serving steps: prefill (full-sequence -> cache), decode (one token
against the cache), and multi-token speculative verification.

Four program flavors:

* ``make_decode_step`` — lockstep batch against a contiguous cache; its
  ``greedy_generate`` driver is the *parity oracle* the continuous-
  batching engine (serve/scheduler.py) is token-exact against.
* ``make_paged_decode_step`` — per-request positions against a paged KV
  cache (serve/kv_cache.py); one jit'd program serves every mix of
  requests because the batch/page shapes are fixed.
* ``make_chunk_prefill_step`` — batched masked prompt ingestion
  (chunked prefill): one chunk each for up to B_pf co-ingesting
  requests per dispatch, inactive rows routed to the null page;
  context length bucketed by the scheduler.
* ``make_verify_step`` — score T = k+1 tokens per request in one pass
  (speculative decode); T = 1 is bit-for-bit one paged decode step.
* ``make_fused_step`` — the decode/verify rows AND the chunked-prefill
  rows of one engine step in a single dispatch (the steady-state
  uber-program); each half is bit-identical to its standalone program
  (models/lm.fused_step_paged spells out the disjointness argument).

Invariants every program in this module preserves (the engine's parity
guarantee composes out of them — docs/serving.md):

* **Fixed shapes, traced values** — batch size, chunk size, page-table
  width (per bucket), and T are compile-time constants; positions,
  lengths, and page ids are traced.  One compile serves every request
  mix, so numerics can never depend on *which* requests are batched.
* **Greedy argmax at the program boundary** — token selection happens
  inside the jit'd program in f32 logits; the host only ever sees
  int32 token ids, never logits to re-reduce.
* **The caller owns authoritative lengths/tables** — programs treat
  ``state["lengths"]`` / ``state["page_tables"]`` as read-only inputs;
  host bookkeeping in serve/kv_cache.py is the source of truth, which
  is what lets verification advance a *variable* number of positions
  per step.
* **The step programs never write the pools** — they read
  ``state["k_pages"]`` / ``state["v_pages"]`` and return ``writes``
  (the step's new K/V and their (page, slot) targets); the engine
  hands those to ``ServePrograms.write_pages``, the one program that
  takes the pools (donated) and stores into them in place.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["make_prefill_step", "make_decode_step",
           "make_paged_decode_step", "make_chunk_prefill_step",
           "make_verify_step", "make_fused_step", "greedy_generate",
           "ServePrograms"]


def _sample(logits, sample: str):
    """Token ids from the last axis of ``logits``, under the ``sample``
    scope (greedy is the only rule)."""
    if sample != "greedy":
        raise ValueError(sample)
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def make_prefill_step(model, max_len=None) -> Callable:
    def prefill_step(params, batch):
        return model.prefill(params, batch, max_len=max_len)
    return prefill_step


def make_decode_step(model, sample: str = "greedy") -> Callable:
    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        return _sample(logits, sample)[:, None], cache
    return serve_step


def make_paged_decode_step(model, sample: str = "greedy",
                           tp_axis: Optional[str] = None) -> Callable:
    def paged_step(params, state, tokens):
        logits, writes = model.decode_paged(params, state, tokens,
                                            tp_axis=tp_axis)
        return _sample(logits, sample)[:, None], writes
    return paged_step


def make_verify_step(model, sample: str = "greedy",
                     tp_axis: Optional[str] = None) -> Callable:
    """Speculative-verification step: score T tokens per request in one
    batched pass (token 0 = last confirmed token, 1..T-1 = draft) and
    return (greedy next-token ids (B, T), writes).  Row b's
    ``nxt[b, t]`` is the target model's own prediction after consuming
    tokens 0..t — the host accepts the longest draft prefix that
    matches and takes ``nxt[b, a]`` as the free bonus token."""
    def verify_step(params, state, tokens):
        logits, writes = model.verify_paged(params, state, tokens,
                                            tp_axis=tp_axis)
        return _sample(logits, sample), writes
    return verify_step


def make_chunk_prefill_step(model, sample: str = "greedy",
                            tp_axis: Optional[str] = None) -> Callable:
    """Batched chunked-prefill step: ingest up to C prompt tokens each
    for up to B_pf requests in ONE dispatch and return (greedy next
    tokens (B_pf, 1), writes).  Rows with ``n_valid[b] == 0`` are
    inactive (null-page routed); a row's token is only meaningful on
    the chunk that completes its prompt (it is that request's first
    generated token); other rows' logits are discarded by the engine.
    Which requests share a dispatch can never change a row's numerics
    (models/lm.prefill_chunk_paged)."""
    def chunk_step(params, state, tokens, table_rows, starts, n_valid):
        logits, writes = model.chunk_paged(
            params, state, tokens, table_rows, starts, n_valid,
            tp_axis=tp_axis)
        return _sample(logits, sample)[:, None], writes
    return chunk_step


def make_fused_step(model, sample: str = "greedy",
                    tp_axis: Optional[str] = None) -> Callable:
    """Fused engine step: one dispatch covering both halves of a
    steady-state iteration — the decode/verify rows (``tokens``
    (B, T) against ``state``'s tables/lengths) and the chunked-prefill
    rows (``p_tokens`` (B_pf, C) with their table rows / starts /
    valid counts).  Returns ``((d_nxt (B, T), p_nxt (B_pf, 1)),
    writes)``: ``d_nxt`` is exactly what the decode (T == 1) or
    verify (T > 1) program would return, ``p_nxt`` exactly what the
    chunked-prefill program would return — the scheduler applies both
    with the same host logic as the unfused paths."""
    def fused_step(params, state, tokens, p_tokens, p_table_rows,
                   p_starts, p_n_valid):
        (d_logits, p_logits), writes = model.fused_paged(
            params, state, tokens, p_tokens, p_table_rows, p_starts,
            p_n_valid, tp_axis=tp_axis)
        return (_sample(d_logits, sample),
                _sample(p_logits, sample)[:, None]), writes
    return fused_step


class ServePrograms:
    """The jit-compiled serving programs (decode / chunked prefill /
    verify / fused, and the page writer) for one model, independent of
    any engine instance.

    Engines historically built their own ``jax.jit`` wrappers, which
    meant N replicas of the same model paid N compiles of the *same*
    program (jit caches are per-wrapper) — measured as the dominant
    cost of a multi-replica run at smoke sizes.  A ``ServePrograms``
    is built once and shared: every ``ServeEngine(programs=...)``
    reuses one compile cache across replicas.  The verify program is
    built lazily so non-speculative engines never trace it.

    The step programs read the page pools and never return them: each
    returns its tokens and ``writes``, the step's new K/V with their
    (page, slot) targets (models/lm.write_pages).  ``write_pages``
    alone takes the pools, donated, and stores the writes in place, so
    a step holds one copy of each pool and the step programs' own
    arguments all stay alive.

    The tensor-parallel counterpart (same attribute surface, programs
    shard_map'd over a mesh) is serve/parallel.py's
    ``TPServePrograms``; the engine treats the two interchangeably.
    """

    tp = 1          # single-device: no mesh

    def __init__(self, model):
        self.model = model
        self.decode = jax.jit(make_paged_decode_step(model))
        self.chunk = jax.jit(make_chunk_prefill_step(model))
        self.write_pages = jax.jit(model.write_pages, donate_argnums=(0, 1))
        self._verify = None
        self._fused = None
        self._params_cache: dict = {}

    @property
    def verify(self):
        if self._verify is None:
            self._verify = jax.jit(make_verify_step(self.model))
        return self._verify

    @property
    def fused(self):
        # lazy like verify: --no-fused engines never trace it
        if self._fused is None:
            self._fused = jax.jit(make_fused_step(self.model))
        return self._fused

    # placement hooks (overridden by TPServePrograms).  ``device`` is
    # the engine's own chip (None: the default device); the programs
    # run where their committed params and pages live, so router
    # replicas on distinct devices share this one bundle.
    def prepare_params(self, params, device=None):
        """``params`` committed to ``device`` (one copy per (tree,
        device), shared by every engine placed there; the original
        stays referenced so a recycled id can never alias a dead
        tree)."""
        if device is None:
            return params
        key = (id(params), device)
        if key not in self._params_cache:
            self._params_cache[key] = (params,
                                       jax.device_put(params, device))
        return self._params_cache[key][1]

    def page_sharding(self, device=None):
        """Where the engine's page pool is allocated: committed to
        ``device`` (the default device where None), so every step's
        writes come back committed there too and the writer compiled
        at the engine's build serves them."""
        return jax.sharding.SingleDeviceSharding(
            device if device is not None else jax.devices()[0])

    def index_sharding(self, device=None):
        """Where a step's write targets live (with its K/V, here)."""
        return self.page_sharding(device)


def greedy_generate(model, params, prompt_batch, n_steps: int,
                    cache_len: int):
    """Batched greedy decoding driver (example path, jit'd per step)."""
    step = jax.jit(make_decode_step(model))
    max_len = max(cache_len, prompt_batch["tokens"].shape[1] + n_steps)
    last, cache = jax.jit(make_prefill_step(model, max_len=max_len))(
        params, prompt_batch)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    for _ in range(n_steps - 1):
        tok, cache = step(params, cache, tok)
        out.append(tok)
    return jnp.concatenate(out, axis=1)
