"""Unified decoder-only LM covering the dense / MoE / hybrid / SSM / VLM
architectures of the assigned pool.

A model is a stack of blocks; each block is `mix` (attention, local
attention, RG-LRU or RWKV time-mix) + `ffn` (SwiGLU / GELU MLP, MoE, or
RWKV channel-mix), pre-normed with residual adds.  Homogeneous stacks
are `lax.scan`'d over stacked parameters (compile-time O(1) in depth —
mandatory for the 80-layer config under 512-way SPMD); heterogeneous
stacks (recurrentgemma's 1:2 pattern, DeepSeek's leading dense layer)
unroll.

Three entry points, matching the assigned input shapes:

* ``apply``       — logits over a full sequence (training fwd).
* ``prefill``     — same math + returns a decode cache.
* ``decode_step`` — one token against the cache (serve_step).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..sharding import constrain
from .base import ParamSpec, init_params, abstract_params
from . import components as C
from . import rglru as R
from . import rwkv6 as W

__all__ = ["DecoderLM"]


def _stack_specs(spec_tree, n: int):
    """Prefix every leaf with a stacked ("layers",) axis."""
    return jax.tree.map(
        lambda ps: ParamSpec((n,) + ps.shape, ("layers",) + ps.axes,
                             ps.dtype, ps.init),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


class DecoderLM:
    def __init__(self, cfg):
        self.cfg = cfg
        kinds = cfg.layer_kinds
        first_dense = cfg.moe.first_dense if cfg.moe else 0
        # scan when every layer is structurally identical
        self.scanned = len(set(kinds)) == 1 and first_dense in (0,)
        self.first_dense = first_dense
        if first_dense:
            self.scanned = len(set(kinds[first_dense:])) == 1
        self.kinds = kinds

    # ------------------------------------------------------------------
    # parameter specs
    # ------------------------------------------------------------------
    def _block_specs(self, kind: str, use_moe: bool,
                     dense_ff: Optional[int] = None) -> dict:
        cfg = self.cfg
        s: Dict[str, Any] = {"ln1": C.norm_specs(cfg.d_model, cfg.norm_kind)}
        if kind in ("attn", "local_attn"):
            s["mix"] = C.attn_specs(cfg)
        elif kind == "rglru":
            s["mix"] = R.rglru_block_specs(cfg)
        elif kind == "rwkv":
            s["mix"] = W.rwkv_time_specs(cfg)
        else:
            raise ValueError(kind)
        s["ln2"] = C.norm_specs(cfg.d_model, cfg.norm_kind)
        if kind == "rwkv":
            s["ffn"] = W.rwkv_ffn_specs(cfg)
        elif use_moe:
            s["ffn"] = C.moe_specs(cfg)
        else:
            s["ffn"] = C.mlp_specs(cfg, dense_ff)
        return s

    def _layer_uses_moe(self, i: int) -> bool:
        return self.cfg.moe is not None and i >= self.first_dense

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs = self._param_specs_f32()
        from .base import with_param_dtype
        return with_param_dtype(specs, cfg.param_dtype)

    def _param_specs_f32(self) -> dict:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "embed": C.embed_specs(cfg),
            "final_norm": C.norm_specs(cfg.d_model, cfg.norm_kind),
        }
        if self.scanned:
            n = cfg.n_layers - self.first_dense
            body = self._block_specs(self.kinds[-1],
                                     cfg.moe is not None)
            specs["layers"] = _stack_specs(body, n)
            for i in range(self.first_dense):
                specs[f"dense_layer_{i}"] = self._block_specs(
                    self.kinds[i], False, cfg.moe.dense_d_ff)
        else:
            for i, kind in enumerate(self.kinds):
                specs[f"layer_{i:02d}"] = self._block_specs(
                    kind, self._layer_uses_moe(i))
        return specs

    def init(self, rng: jax.Array):
        return init_params(self.param_specs(), rng)

    def abstract(self):
        return abstract_params(self.param_specs())

    # ------------------------------------------------------------------
    # block application
    # ------------------------------------------------------------------
    def _apply_block(self, kind: str, use_moe: bool, p, x, *,
                     positions, mrope_positions, cache, cache_pos, train):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        h = C.apply_norm(p["ln1"], x, cfg.norm_kind, cfg.norm_eps)
        new_cache: Dict[str, Any] = {}
        if kind in ("attn", "local_attn"):
            window = cfg.local_window if kind == "local_attn" else None
            mix, kv = C.attention_block(
                p["mix"], h, cfg, positions=positions, window=window,
                mrope_positions=mrope_positions,
                cache=None if cache is None else cache["kv"],
                cache_pos=cache_pos)
            if window is not None and cache is None:       # prefill->ring
                kv = {"k": kv["k"][:, -window:], "v": kv["v"][:, -window:]}
            new_cache["kv"] = kv
        elif kind == "rglru":
            mix, rec = R.rglru_block(
                p["mix"], h, cfg,
                state=None if cache is None else cache["rec"])
            new_cache["rec"] = rec
        else:  # rwkv
            mix, att = W.rwkv_time_block(
                p["mix"], h, cfg,
                state=None if cache is None else cache["att"])
            new_cache["att"] = att
        x = x + mix
        h2 = C.apply_norm(p["ln2"], x, cfg.norm_kind, cfg.norm_eps)
        if kind == "rwkv":
            f, ffn = W.rwkv_channel_block(
                p["ffn"], h2, cfg,
                state=None if cache is None else cache["ffn"])
            new_cache["ffn"] = ffn
        elif use_moe:
            f, aux = C.moe_block(p["ffn"], h2, cfg)
        else:
            f = C.mlp_block(p["ffn"], h2, cfg)
        x = x + f
        x = constrain(x, ("batch", "seq", "act_embed"))
        return x, aux, new_cache

    # ------------------------------------------------------------------
    # forward entry points
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch, dtype):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = C.embed_tokens(params["embed"], batch["tokens"], cfg, dtype)
            if cfg.n_patches and "patch_embeds" in batch:
                # VLM stub frontend: precomputed patch embeddings replace
                # the leading placeholder tokens (brief: frontend is a
                # stub).
                pe = batch["patch_embeds"].astype(dtype)
                x = lax.dynamic_update_slice(x, pe, (0, 0, 0))
        return x

    def _positions(self, batch):
        B, S = batch["tokens"].shape
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
        return pos

    def apply(self, params, batch, *, train: bool = True,
              want_cache: bool = False, want_hidden: bool = False):
        """Full-sequence forward.  Returns (logits, aux_dict); with
        ``want_hidden`` returns the final-norm hidden states instead of
        logits (the chunked-loss path never materializes (B,S,V))."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        x = self._embed_inputs(params, batch, dtype)
        positions = self._positions(batch)
        mrope = batch.get("mrope_positions")
        aux_total = jnp.zeros((), jnp.float32)
        caches: Dict[str, Any] = {}

        # leading unscanned dense layers (DeepSeek pattern)
        for i in range(self.first_dense):
            blk = functools.partial(
                self._apply_block, self.kinds[i], False,
                positions=positions, mrope_positions=mrope,
                cache=None, cache_pos=None, train=train)
            if train and cfg.remat == "full":
                blk = jax.checkpoint(blk)
            x, aux, c = blk(params[f"dense_layer_{i}"], x)
            aux_total += aux
            caches[f"dense_layer_{i}"] = c

        if self.scanned:
            kind = self.kinds[-1]
            use_moe = cfg.moe is not None

            def body(x, lp):
                y, aux, c = self._apply_block(
                    kind, use_moe, lp, x, positions=positions,
                    mrope_positions=mrope, cache=None, cache_pos=None,
                    train=train)
                if not want_cache:
                    c = None
                return y, (aux, c)

            if train and cfg.remat == "full":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, (auxs, cs) = lax.scan(body, x, params["layers"])
            aux_total += auxs.sum()
            if want_cache:
                caches["layers"] = cs
        else:
            for i in range(self.first_dense, cfg.n_layers):
                blk = functools.partial(
                    self._apply_block, self.kinds[i], self._layer_uses_moe(i),
                    positions=positions, mrope_positions=mrope,
                    cache=None, cache_pos=None, train=train)
                if train and cfg.remat == "full":
                    blk = jax.checkpoint(blk)
                x, aux, c = blk(params[f"layer_{i:02d}"], x)
                aux_total += aux
                caches[f"layer_{i:02d}"] = c

        x = C.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        out_aux = {"moe_aux": aux_total}
        if want_hidden:
            return x, out_aux
        logits = C.unembed(params["embed"], x, cfg)
        if want_cache:
            caches["pos"] = jnp.asarray(batch["tokens"].shape[1], jnp.int32)
            return logits, out_aux, caches
        return logits, out_aux

    def prefill(self, params, batch, *, max_len: Optional[int] = None):
        """Forward + decode cache (the ``prefill_*`` shapes).  Returns
        (last-token logits, cache).  ``max_len`` > prompt length pads
        the full-attention KV caches with decode headroom (ring-buffer
        and recurrent states are fixed-size and need none)."""
        logits, _, cache = self.apply(params, batch, train=False,
                                      want_cache=True)
        S = batch["tokens"].shape[1]
        if max_len is not None and max_len > S:
            cache = self._pad_cache(cache, max_len - S)
        return logits[:, -1], cache

    def _pad_cache(self, cache, extra: int):
        cfg = self.cfg

        def pad_kv(kv, axis):
            pad = [(0, 0)] * kv["k"].ndim
            pad[axis] = (0, extra)
            return {n: jnp.pad(kv[n], pad) for n in ("k", "v")}

        out = dict(cache)
        if self.scanned and self.kinds[-1] == "attn":
            out["layers"] = dict(cache["layers"])
            out["layers"]["kv"] = pad_kv(cache["layers"]["kv"], axis=2)
        elif not self.scanned:
            for i in range(self.first_dense, cfg.n_layers):
                name = f"layer_{i:02d}"
                if self.kinds[i] == "attn":
                    out[name] = dict(cache[name])
                    out[name]["kv"] = pad_kv(cache[name]["kv"], axis=1)
        for i in range(self.first_dense):         # leading dense layers
            name = f"dense_layer_{i}"
            if self.kinds[i] == "attn":
                out[name] = dict(cache[name])
                out[name]["kv"] = pad_kv(cache[name]["kv"], axis=1)
        return out

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _block_cache_specs(self, kind: str, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        if kind == "attn":
            shp = (batch, seq_len, cfg.n_kv_heads, cfg.head_dim)
            ax = ("batch", "kv_seq", "act_heads", None)
            return {"kv": {"k": ParamSpec(shp, ax, jnp.bfloat16),
                           "v": ParamSpec(shp, ax, jnp.bfloat16)}}
        if kind == "local_attn":
            w = min(cfg.local_window, seq_len)
            shp = (batch, w, cfg.n_kv_heads, cfg.head_dim)
            ax = ("batch", "kv_seq", "act_heads", None)
            return {"kv": {"k": ParamSpec(shp, ax, jnp.bfloat16),
                           "v": ParamSpec(shp, ax, jnp.bfloat16)}}
        if kind == "rglru":
            return {"rec": R.rglru_state_specs(cfg, batch)}
        if kind == "rwkv":
            s = W.rwkv_state_specs(cfg, batch)
            return {
                "att": {"shift": s["att_shift"], "wkv": s["wkv"]},
                "ffn": {"shift": s["ffn_shift"]},
            }
        raise ValueError(kind)

    def cache_specs(self, batch: int, seq_len: int) -> dict:
        """ParamSpec tree for a decode cache of capacity ``seq_len``."""
        cfg = self.cfg
        specs: Dict[str, Any] = {}
        if self.scanned:
            n = cfg.n_layers - self.first_dense
            specs["layers"] = _stack_specs(
                self._block_cache_specs(self.kinds[-1], batch, seq_len), n)
            for i in range(self.first_dense):
                specs[f"dense_layer_{i}"] = self._block_cache_specs(
                    self.kinds[i], batch, seq_len)
        else:
            for i, kind in enumerate(self.kinds):
                specs[f"layer_{i:02d}"] = self._block_cache_specs(
                    kind, batch, seq_len)
        specs["pos"] = ParamSpec((), (), jnp.int32)
        return specs

    def init_cache(self, batch: int, seq_len: int):
        return jax.tree.map(
            lambda ps: jnp.zeros(ps.shape, ps.dtype),
            self.cache_specs(batch, seq_len),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    def decode_step(self, params, cache, tokens):
        """One decode step.  tokens: (B, 1).  Returns (logits, new_cache)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        pos = cache["pos"]                                  # scalar
        B = tokens.shape[0]
        batch = {"tokens": tokens,
                 "positions": jnp.full((B, 1), pos, jnp.int32)}
        if cfg.rope_kind == "mrope":
            # text-only decode: all three m-rope ids advance with t
            batch["mrope_positions"] = jnp.full((B, 3, 1), pos, jnp.int32)
        x = self._embed_inputs(params, batch, dtype)
        positions = batch["positions"]
        mrope = batch.get("mrope_positions")
        new_cache: Dict[str, Any] = {"pos": pos + 1}

        for i in range(self.first_dense):
            x, _, c = self._apply_block(
                self.kinds[i], False, params[f"dense_layer_{i}"], x,
                positions=positions, mrope_positions=mrope,
                cache=cache[f"dense_layer_{i}"], cache_pos=pos, train=False)
            new_cache[f"dense_layer_{i}"] = c

        if self.scanned:
            kind = self.kinds[-1]
            use_moe = cfg.moe is not None

            def body(x, inp):
                lp, lc = inp
                y, _, c = self._apply_block(
                    kind, use_moe, lp, x, positions=positions,
                    mrope_positions=mrope, cache=lc, cache_pos=pos,
                    train=False)
                return y, c
            x, cs = lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache["layers"] = cs
        else:
            for i in range(self.first_dense, cfg.n_layers):
                x, _, c = self._apply_block(
                    self.kinds[i], self._layer_uses_moe(i),
                    params[f"layer_{i:02d}"], x, positions=positions,
                    mrope_positions=mrope, cache=cache[f"layer_{i:02d}"],
                    cache_pos=pos, train=False)
                new_cache[f"layer_{i:02d}"] = c

        x = C.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
        logits = C.unembed(params["embed"], x, cfg)
        return logits[:, 0], new_cache

    # ------------------------------------------------------------------
    # paged decode (continuous batching)
    # ------------------------------------------------------------------
    def supports_paged_decode(self) -> bool:
        """Paged decode covers scanned full-attention stacks (the dense
        GQA family).  Ring-buffer and recurrent-state families have
        fixed-size caches — paging buys them nothing."""
        return (self.scanned and self.first_dense == 0
                and set(self.kinds) == {"attn"}
                and self.cfg.rope_kind != "mrope")

    def paged_state_specs(self, batch: int, *, n_pages: int,
                          page_size: int, max_pages_per_seq: int,
                          shards: int = 1) -> dict:
        """The paged serve state; ``shards`` devices split the pools'
        head axis (tensor-parallel serving, serve/parallel.py)."""
        cfg = self.cfg
        kvh, dh = cfg.n_kv_heads, cfg.head_dim
        # a token's K (or V) in its page: the (KVH, Dh) slab where one
        # shard's slab (KVH / shards, Dh) fills whole (8, 128) memory
        # tiles of the chip, else one row of KVH * Dh (heads major).  A
        # slab that pads its tiles makes the chip's default layout put
        # the page axis minor, which no page gather or scatter can read
        # in place; a row that the attention must split into heads
        # costs a relayout of every gathered buffer.  Either way each
        # shard's pool keeps the row-major layout that the step programs
        # gather from and the writer scatters into.
        tiled = (kvh // shards) % 8 == 0 and dh % 128 == 0
        shp = (cfg.n_layers, n_pages, page_size) + (
            (kvh, dh) if tiled else (kvh * dh,))
        ax = ("layers", None, "kv_seq", "act_heads") + (
            (None,) if tiled else ())
        return {
            "k_pages": ParamSpec(shp, ax, jnp.bfloat16),
            "v_pages": ParamSpec(shp, ax, jnp.bfloat16),
            "page_tables": ParamSpec((batch, max_pages_per_seq),
                                     ("batch", None), jnp.int32),
            "lengths": ParamSpec((batch,), ("batch",), jnp.int32),
        }

    # The paged step programs name their parts with ``jax.named_scope``
    # (a device op's scope path in the profiler trace, e.g.
    # ``jit(fused_step)/while/body/attn/attn.core/...``): ``embed``;
    # per layer ``norm``, ``attn`` (children ``attn.gather``,
    # ``attn.core``, ``attn.kv_write``: the step's own K/V overlaid on
    # the gathered pages) and ``mlp``; after the layer scan
    # ``kv_scatter`` (the write targets), ``norm`` and ``unembed``;
    # ``sample`` in serve/step.py; ``kv_scatter`` again in the page
    # writer.  The scan's own slicing of its inputs (the stacked weights
    # and the layer index) and stacking of its outputs (the new K/V)
    # stay outside every scope, so the layer loop's unscoped ops are
    # that traffic.
    #
    # Write order: each program is "compute, then write".  The compute
    # half (``decode_paged``, ``verify_paged``, ``chunk_paged``,
    # ``fused_paged``) reads the incoming pools, closed over and
    # read-only, and returns the step's new K/V with their (page, slot)
    # targets; ``write_pages`` persists them, one stacked scatter over
    # all layers per row group.  The serve engine runs the two as
    # separate programs so that the writer alone takes the pools
    # donated (serve/step.ServePrograms.write_pages); the
    # ``*_step_paged`` methods compose them in one.
    def _paged_layer(self, lp, x, attend, tp_axis):
        """One layer of a paged step program on the residual stream
        ``x``: ``attend(h)`` is the attention sub-layer on the normed
        input, returning ``(out, *extra)``.  Returns (x, extra)."""
        cfg = self.cfg
        with jax.named_scope("norm"):
            h = C.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
        with jax.named_scope("attn"):
            mix, *extra = attend(h)
            x = x + mix
        with jax.named_scope("norm"):
            h = C.apply_norm(lp["ln2"], x, cfg.norm_kind, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if cfg.moe is not None:
                f, _ = C.moe_block(lp["ffn"], h, cfg)
            else:
                f = C.mlp_block(lp["ffn"], h, cfg, tp_axis=tp_axis)
            x = x + f
        return x, extra

    def _paged_logits(self, params, x, n_valid=None):
        """Final norm and unembedding of ``x`` (B, T, D); with
        ``n_valid`` (B,), of each row's last valid token only."""
        cfg = self.cfg
        with jax.named_scope("norm"):
            x = C.apply_norm(params["final_norm"], x, cfg.norm_kind,
                             cfg.norm_eps)
        with jax.named_scope("unembed"):
            if n_valid is not None:
                x = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)
            return C.unembed(params["embed"], x, cfg)

    def _paged_scan(self, body, carry, params):
        """The layer loop of a paged step program: ``body(carry, (lp,
        layer))`` over the stacked weights and the layer index only;
        the page pools stay closed over, read-only, so the loop never
        slices, restacks or copies a pool."""
        layers = jnp.arange(self.cfg.n_layers, dtype=jnp.int32)
        return lax.scan(body, carry, (params["layers"], layers))

    @staticmethod
    def write_pages(k_pages, v_pages, writes):
        """Persist a step's new K/V: ``writes`` is a tuple of row groups
        ``{"k", "v": (L, B, T, KVH, Dh); "page", "slot": (B, T)}`` (as
        the compute halves return them), each written for every layer
        in one stacked scatter, in order.  The scatter stores whole
        tokens into the pool viewed as (L * P * ps, <token>), at
        ``(layer * P + page) * ps + slot`` — the view merges only the
        pool's leading axes, so it is the pool's own layout and nothing
        is copied.  Targets past a row's table or valid tokens are the
        null page (``chunk_scatter_targets`` /
        ``verify_scatter_targets``); the groups' other targets are
        disjoint (see ``fused_paged``), so the order shows only on the
        null page.  Returns (k_pages, v_pages)."""
        L, P, ps = k_pages.shape[:3]

        def store(pages, new, rows):
            token = pages.shape[3:]
            flat = pages.reshape(L * P * ps, *token)
            flat = flat.at[rows].set(new.reshape(rows.size, *token))
            return flat.reshape(pages.shape)

        with jax.named_scope("kv_scatter"):
            layer = jnp.arange(L, dtype=jnp.int32)[:, None, None]
            for w in writes:
                rows = ((layer * P + w["page"]) * ps + w["slot"]).ravel()
                k_pages = store(k_pages, w["k"], rows)
                v_pages = store(v_pages, w["v"], rows)
        return k_pages, v_pages

    @staticmethod
    def _decode_group(ks, vs, state, T):
        """The decode/verify rows' writes: token t of row b at position
        ``lengths[b] + t`` (``verify_scatter_targets``)."""
        with jax.named_scope("kv_scatter"):
            page, slot = C.verify_scatter_targets(
                state["lengths"], state["page_tables"], T,
                state["k_pages"].shape[2])
        return {"k": ks, "v": vs, "page": page, "slot": slot}

    @staticmethod
    def _chunk_group(ks, vs, state, starts, n_valid, table_rows):
        """The prefill rows' writes (``chunk_scatter_targets``): write
        targets are private per row (COW at admission), so co-ingested
        rows never write into each other."""
        with jax.named_scope("kv_scatter"):
            page, slot = C.chunk_scatter_targets(
                starts, n_valid, table_rows, ks.shape[2],
                state["k_pages"].shape[2])
        return {"k": ks, "v": vs, "page": page, "slot": slot}

    def _written(self, state, writes, **rest):
        k_pages, v_pages = self.write_pages(state["k_pages"],
                                            state["v_pages"], writes)
        return {"k_pages": k_pages, "v_pages": v_pages, **rest}

    def chunk_paged(self, params, state, tokens, table_rows, starts,
                    n_valid, tp_axis=None):
        """The compute half of ``prefill_chunk_paged``: (per-row
        last-valid-token logits (B, V), writes)."""
        assert self.supports_paged_decode()
        cfg = self.cfg
        assert not (tp_axis is not None and cfg.moe is not None)
        dtype = jnp.dtype(cfg.compute_dtype)
        n = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
        x = self._embed_inputs(
            params, {"tokens": tokens, "positions": positions}, dtype)

        def body(x, inp):
            lp, layer = inp
            return self._paged_layer(
                lp, x, lambda h: C.paged_chunk_attention_block(
                    lp["mix"], h, cfg, positions=positions, starts=starts,
                    n_valid=n_valid, k_pages=state["k_pages"],
                    v_pages=state["v_pages"], layer=layer,
                    table_rows=table_rows, tp_axis=tp_axis), tp_axis)

        x, (ks, vs) = self._paged_scan(body, x, params)
        writes = (self._chunk_group(ks, vs, state, starts, n_valid,
                                    table_rows),)
        logits = self._paged_logits(params, x, n_valid)
        return logits[:, 0], writes

    def prefill_chunk_paged(self, params, state, tokens, table_rows,
                            starts, n_valid, tp_axis=None):
        """Ingest one prompt chunk each for up to B requests into the
        paged KV cache (batched chunked prefill) in one dispatch.

        ``tokens``: (B, C) — row b holds its request's next C prompt
        tokens at absolute positions ``starts[b] + t``; tokens with
        t >= ``n_valid[b]`` are padding and rows with
        ``n_valid[b] == 0`` are inactive (their K/V writes land on the
        null page).  ``table_rows``: (B, nb) int32 — each request's
        page table truncated to the dispatch's context bucket, null
        beyond a row's own pages.  ``starts`` / ``n_valid``: (B,)
        traced int32, so one compile serves every mix of chunks in the
        bucket — which requests co-ingest can never change numerics.
        Returns (per-row last-valid-token logits (B, V), new page
        state); a row's logits are only meaningful on the chunk that
        completes its prompt.  ``chunk_paged`` then ``write_pages``.

        Token-exactness: the flash partition is anchored at absolute
        position 0, the K/V gathered back from pages carry the same
        bf16 bits whole-prompt prefill would have produced (compute
        dtype == page dtype), and every other op is per-(row, token) —
        so any chunking of a prompt, dispatched alone or co-batched,
        reproduces ``prefill``'s last-token logits and cache
        bit-for-bit (components.paged_chunk_attention_block).

        ``tp_axis``: mesh axis name when running as the per-shard body
        of a tensor-parallel ``shard_map`` program (serve/parallel.py;
        ``self`` is then the shard-local model view).
        """
        logits, writes = self.chunk_paged(params, state, tokens,
                                          table_rows, starts, n_valid,
                                          tp_axis=tp_axis)
        return logits, self._written(state, writes)

    def decode_paged(self, params, state, tokens, tp_axis=None):
        """The compute half of ``decode_step_paged``: (logits (B, V),
        writes)."""
        assert self.supports_paged_decode()
        cfg = self.cfg
        assert not (tp_axis is not None and cfg.moe is not None)
        dtype = jnp.dtype(cfg.compute_dtype)
        lengths = state["lengths"]
        tables = state["page_tables"]
        positions = lengths[:, None].astype(jnp.int32)
        x = self._embed_inputs(
            params, {"tokens": tokens, "positions": positions}, dtype)

        def body(x, inp):
            lp, layer = inp
            return self._paged_layer(
                lp, x, lambda h: C.paged_attention_block(
                    lp["mix"], h, cfg, positions=positions,
                    k_pages=state["k_pages"], v_pages=state["v_pages"],
                    layer=layer, page_table=tables, lengths=lengths,
                    tp_axis=tp_axis), tp_axis)

        x, (ks, vs) = self._paged_scan(body, x, params)
        writes = (self._decode_group(ks, vs, state, 1),)
        logits = self._paged_logits(params, x)
        return logits[:, 0], writes

    def decode_step_paged(self, params, state, tokens, tp_axis=None):
        """One continuous-batching decode step against a paged KV cache.

        ``state``: {k_pages, v_pages: (L, P, ps, KVH, Dh); page_tables:
        (B, n) int32; lengths: (B,) int32}.  ``tokens``: (B, 1).  Each
        sequence decodes at its own position ``lengths[b]`` (no
        lockstep).  Returns (logits (B, V), new state) with lengths
        advanced; callers that mask inactive slots (the serve engine)
        own the authoritative lengths host-side.  ``decode_paged``
        then ``write_pages``.

        ``tp_axis``: mesh axis name when running as the per-shard body
        of a tensor-parallel ``shard_map`` program (serve/parallel.py).
        """
        logits, writes = self.decode_paged(params, state, tokens,
                                           tp_axis=tp_axis)
        return logits, self._written(state, writes,
                                     page_tables=state["page_tables"],
                                     lengths=state["lengths"] + 1)

    def verify_paged(self, params, state, tokens, tp_axis=None):
        """The compute half of ``verify_step_paged``: (logits (B, T, V),
        writes)."""
        assert self.supports_paged_decode()
        cfg = self.cfg
        assert not (tp_axis is not None and cfg.moe is not None)
        dtype = jnp.dtype(cfg.compute_dtype)
        lengths = state["lengths"]
        tables = state["page_tables"]
        B, T = tokens.shape
        positions = (lengths[:, None]
                     + jnp.arange(T, dtype=jnp.int32)[None, :])
        x = self._embed_inputs(
            params, {"tokens": tokens, "positions": positions}, dtype)

        def body(x, inp):
            lp, layer = inp
            return self._paged_layer(
                lp, x, lambda h: C.paged_verify_attention_block(
                    lp["mix"], h, cfg, positions=positions,
                    k_pages=state["k_pages"], v_pages=state["v_pages"],
                    layer=layer, page_table=tables, lengths=lengths,
                    tp_axis=tp_axis), tp_axis)

        x, (ks, vs) = self._paged_scan(body, x, params)
        writes = (self._decode_group(ks, vs, state, T),)
        return self._paged_logits(params, x), writes

    def verify_step_paged(self, params, state, tokens, tp_axis=None):
        """Score T tokens per request in one batched pass against the
        paged KV cache (speculative-decode verification).

        ``tokens``: (B, T) — row b's token 0 is its last confirmed
        token, tokens 1..T-1 a draft continuation; token t sits at the
        per-request absolute position ``lengths[b] + t``.  All T
        tokens' K/V are persisted into pages and each query attends
        causally up to its own position, so ``logits[:, t]`` is
        bit-identical to what ``decode_step_paged`` would return after
        sequentially consuming tokens 0..t (same per-token projections,
        same gathered-buffer softmax shape — docs/speculative.md spells
        out the argument).  T = 1 degenerates to exactly one decode
        step.  ``verify_paged`` then ``write_pages``.

        Returns (logits (B, T, V), new state).  ``lengths`` is returned
        *unadvanced*: how many of the T positions become real history
        depends on host-side acceptance, and the caller (serve
        scheduler) owns the authoritative lengths — rejected positions
        hold stale page writes that masking hides, like any slot past
        ``lengths``.
        """
        logits, writes = self.verify_paged(params, state, tokens,
                                           tp_axis=tp_axis)
        return logits, self._written(state, writes,
                                     page_tables=state["page_tables"],
                                     lengths=state["lengths"])

    def fused_paged(self, params, state, d_tokens, p_tokens, p_table_rows,
                    p_starts, p_n_valid, tp_axis=None):
        """The compute half of ``fused_step_paged``: ((d_logits (B, T,
        V), p_logits (Bp, V)), writes), the decode rows' group first."""
        assert self.supports_paged_decode()
        cfg = self.cfg
        assert not (tp_axis is not None and cfg.moe is not None)
        dtype = jnp.dtype(cfg.compute_dtype)
        lengths = state["lengths"]
        tables = state["page_tables"]
        B, T = d_tokens.shape
        Cn = p_tokens.shape[1]
        d_positions = (lengths[:, None]
                       + jnp.arange(T, dtype=jnp.int32)[None, :])
        p_positions = (p_starts[:, None]
                       + jnp.arange(Cn, dtype=jnp.int32)[None])
        xd = self._embed_inputs(
            params, {"tokens": d_tokens, "positions": d_positions},
            dtype)
        xp = self._embed_inputs(
            params, {"tokens": p_tokens, "positions": p_positions},
            dtype)
        pools = {"k_pages": state["k_pages"], "v_pages": state["v_pages"]}

        def body(carry, inp):
            xd, xp = carry
            lp, layer = inp
            # both groups read the incoming pages (disjointness, above)
            xp, (pk, pv) = self._paged_layer(
                lp, xp, lambda h: C.paged_chunk_attention_block(
                    lp["mix"], h, cfg, positions=p_positions,
                    starts=p_starts, n_valid=p_n_valid, layer=layer,
                    table_rows=p_table_rows, tp_axis=tp_axis, **pools),
                tp_axis)
            # decode/verify rows: T == 1 verification IS the decode
            # step bit for bit (paged_verify_attention_block), so one
            # body serves both widths
            xd, (dk, dv) = self._paged_layer(
                lp, xd, lambda h: C.paged_verify_attention_block(
                    lp["mix"], h, cfg, positions=d_positions, layer=layer,
                    page_table=tables, lengths=lengths, tp_axis=tp_axis,
                    **pools), tp_axis)
            return (xd, xp), (dk, dv, pk, pv)

        (xd, xp), (dks, dvs, pks, pvs) = self._paged_scan(
            body, (xd, xp), params)
        writes = (self._decode_group(dks, dvs, state, T),
                  self._chunk_group(pks, pvs, state, p_starts, p_n_valid,
                                    p_table_rows))
        d_logits = self._paged_logits(params, xd)
        p_logits = self._paged_logits(params, xp, p_n_valid)[:, 0]
        return (d_logits, p_logits), writes

    def fused_step_paged(self, params, state, d_tokens, p_tokens,
                         p_table_rows, p_starts, p_n_valid,
                         tp_axis=None):
        """One fused engine step: decode/verify every DECODING slot AND
        ingest one prompt chunk for every PREFILLING request in a
        single program dispatch (the steady-state step of the serve
        engine collapses from two launches to one).

        ``d_tokens``: (B, T) — the decode/verify rows, exactly as
        ``decode_step_paged`` (T == 1) / ``verify_step_paged`` (T > 1)
        would receive them, positioned by ``state["lengths"]``.
        ``p_tokens`` / ``p_table_rows`` / ``p_starts`` / ``p_n_valid``:
        the chunked-prefill rows, exactly as ``prefill_chunk_paged``
        would receive them (inactive rows null-routed).  Returns
        ``((d_logits (B, T, V), p_logits (Bp, V)), new state)`` with
        ``lengths`` unadvanced (the host owns authoritative lengths).

        Write order: compute reads the incoming pools; one writer
        persists both halves' disjoint targets (``fused_paged`` then
        ``write_pages``).  Token-exactness vs the two sequential
        dispatches rests on page **write/read disjointness**: prefill
        rows write only into their own private pages (copy-on-write at
        admission; shared trie pages are read-only), decode rows write
        only into pages ``ensure_headroom`` privatized for them, decode
        gathers only active-slot tables (which never contain a prefill
        row's private pages) and prefill gathers only its own table
        prefix (which never contains a decode write target).  Both
        groups therefore read the *incoming* pages — exactly what each
        would see dispatched separately in either order — each with
        its own new tokens overlaid, and their writes land on disjoint
        (page, slot) targets, so the combined update commutes.  Inside
        one step every other op is row-independent
        (components.paged_chunk_attention_block /
        paged_verify_attention_block), so each row is bit-identical to
        its unfused counterpart.
        """
        logits, writes = self.fused_paged(
            params, state, d_tokens, p_tokens, p_table_rows, p_starts,
            p_n_valid, tp_axis=tp_axis)
        return logits, self._written(state, writes,
                                     page_tables=state["page_tables"],
                                     lengths=state["lengths"])
