"""Tensor-parallel serving: the paged decode / verify / chunked-prefill
programs shard_map'd over a ``tp`` mesh axis.

This is the scale-*up* half of distributed serving (serve/router.py is
the scale-*out* half): one engine, its KV memory system and attention
arithmetic sharded across devices.  The sharding layout is chosen so
the sharded engine is **bit-identical** to the single-device one — the
serve stack's token-parity guarantee survives the mesh:

* **What is sharded.**  Attention heads: wq/wk/wv (and their biases)
  are split on the head output dim, so shard i computes heads
  ``[i*H/tp, (i+1)*H/tp)`` — and the paged KV cache splits the same
  way, ``k_pages/v_pages: (L, P, ps, KVH/tp[, Dh])`` per device, which
  is the memory-system scaling that motivates TP serving in the first
  place (a single chip's HBM bounds resident KV; tp chips bound tp×).
  The FFN hidden dim (wg/wu, gelu w1/b1) splits identically.
* **What is replicated.**  Page tables, lengths, tokens, norms, the
  embedding/unembedding table, and the contraction-side projections
  wo / wd (w2).  Every shard therefore holds the *full* residual
  stream and computes the (cheap) unembed redundantly.  The batched
  chunked-prefill program's per-row inputs (table rows, starts, valid
  counts) are control metadata like page tables and stay replicated
  too — only its gathered K/V context and page writes are sharded (on
  KVH, with the pages themselves).
* **Why it is bitwise.**  No cross-shard *reduction* ever runs.  Each
  shard's ops are exactly the head/hidden slice of the single-device
  ops (XLA computes each output element's contraction identically
  regardless of sibling columns), and the only collectives are
  ``all_gather``s — concatenations in mesh order — placed just before
  the replicated wo/wd projections (components._tp_gather_heads).  A
  psum-based megatron layout would be cheaper on interconnect but
  reorders the output-projection summation, breaking parity; on real
  hardware you would trade that consciously (docs/ARCHITECTURE.md).

The per-shard program body is the *unchanged* model code run on a
shard-local view: ``DecoderLM`` over a cfg with ``n_heads``,
``n_kv_heads`` and ``d_ff`` divided by tp (plus ``tp_axis`` gather
hooks).  Host-side scheduling (serve/scheduler.py, serve/kv_cache.py)
is untouched — page ids are device-agnostic, so the allocator, prefix
trie, COW and speculation bookkeeping cannot tell the engine is
sharded.

Scope: the dense scanned-attention family (``supports_paged_decode``
and ``cfg.moe is None`` — moe_block owns its own shard_map, which
cannot nest inside this one); tp must divide n_heads, n_kv_heads and
d_ff.  Development and CI run on forced-host-device CPU meshes
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``); the layout
is device-count-, not device-kind-, specific.
"""
from __future__ import annotations

from typing import Dict, Optional

import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import make_mesh
from ..sharding.rules import SERVE_TP_AXIS, serve_tp_spec
from .step import (make_chunk_prefill_step, make_fused_step,
                   make_paged_decode_step, make_verify_step)

__all__ = ["TPServePrograms", "make_tp_mesh", "validate_tp",
           "tp_param_specs", "PAGE_SPEC"]

#: k_pages/v_pages (L, n_pages, page_size, KVH[, Dh]): sharded on the
#: head (or heads-major row) axis, so each device holds whole KV heads
#: — the serving analogue of the training rules' act_heads.
PAGE_SPEC = P(None, None, None, SERVE_TP_AXIS)

#: one row group of a step's writes (models/lm.write_pages): K/V
#: (L, B, T, KVH, Dh) split on KVH like the pages, targets replicated
_WRITE_SPECS = {"k": PAGE_SPEC, "v": PAGE_SPEC, "page": P(), "slot": P()}


def make_tp_mesh(tp: int):
    """A 1-D ``tp``-axis mesh over the first ``tp`` local devices."""
    n = len(jax.devices())
    if tp > n:
        raise ValueError(f"tp={tp} exceeds {n} visible devices "
                         "(CPU dev: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    return make_mesh((tp,), (SERVE_TP_AXIS,))


def validate_tp(model, tp: int) -> None:
    cfg = model.cfg
    if not model.supports_paged_decode():
        raise ValueError(f"{cfg.name}: tensor-parallel serving covers "
                         "the paged-decode family only")
    if cfg.moe is not None:
        raise ValueError(f"{cfg.name}: MoE FFNs run their own "
                         "shard_map (components.moe_block), which "
                         "cannot nest inside the serving TP program")
    for dim, v in (("n_heads", cfg.n_heads),
                   ("n_kv_heads", cfg.n_kv_heads), ("d_ff", cfg.d_ff)):
        if v % tp:
            raise ValueError(f"{cfg.name}: tp={tp} does not divide "
                             f"{dim}={v}")


def tp_param_specs(model):
    """PartitionSpec pytree mirroring ``model.param_specs()`` under the
    serving TP layout (sharding/rules.serve_tp_spec per leaf)."""
    import jax.tree_util as jtu

    def leaf_spec(path, ps):
        return serve_tp_spec(path[-1].key, len(ps.shape))

    return jtu.tree_map_with_path(
        leaf_spec, model.param_specs(),
        is_leaf=lambda x: hasattr(x, "axes"))


def _local_model(model, tp: int):
    """Shard-local view: the same DecoderLM over a cfg whose sharded
    dims are divided by tp — inside shard_map the param shards *are*
    full tensors of this smaller model, so the model code runs
    unchanged (only the _tp_gather_heads hooks know about the mesh)."""
    cfg = model.cfg
    local = dataclasses.replace(
        cfg, n_heads=cfg.n_heads // tp, n_kv_heads=cfg.n_kv_heads // tp,
        d_ff=cfg.d_ff // tp)
    return type(model)(local)


class TPServePrograms:
    """Sharded counterpart of step.ServePrograms: same attribute
    surface (decode / chunk / verify / fused callables with identical
    signatures, the page writer, prepare_params / page_sharding hooks),
    so ServeEngine uses either interchangeably — and N router replicas
    can share one instance to share one compile cache.

    A step's writes come back as they were computed: their K/V
    (L, B, T, KVH, Dh) split on KVH like the pools (``PAGE_SPEC``
    covers both), their targets replicated.  ``write_pages`` takes the
    pools donated on their ``NamedSharding`` and scatters shard-locally
    (targets index pages and slots, never the head axis)."""

    def __init__(self, model, *, tp: Optional[int] = None, mesh=None):
        if mesh is None:
            if tp is None or tp < 2:
                raise ValueError("TPServePrograms needs tp >= 2 or an "
                                 "explicit mesh")
            mesh = make_tp_mesh(tp)
        if SERVE_TP_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh axes {mesh.axis_names} lack "
                             f"'{SERVE_TP_AXIS}'")
        self.mesh = mesh
        self.tp = mesh.shape[SERVE_TP_AXIS]
        validate_tp(model, self.tp)
        self.model = model
        self._local = _local_model(model, self.tp)
        self._pspecs = tp_param_specs(model)
        kv_state = {"k_pages": PAGE_SPEC, "v_pages": PAGE_SPEC}
        self.decode = self._program(make_paged_decode_step, P())
        # batched chunked prefill: (tokens, table_rows, starts,
        # n_valid) are per-row control metadata — replicated, like the
        # decode program's page tables; the heads of the gathered
        # context and of the writes shard with kv_state
        self.chunk = jax.jit(jax.shard_map(
            make_chunk_prefill_step(self._local, tp_axis=SERVE_TP_AXIS),
            mesh=mesh,
            in_specs=(self._pspecs, kv_state, P(), P(), P(), P()),
            out_specs=(P(), (_WRITE_SPECS,)), check_vma=False))
        pages = NamedSharding(mesh, PAGE_SPEC)
        self.write_pages = jax.jit(model.write_pages, donate_argnums=(0, 1),
                                   out_shardings=(pages, pages))
        self._verify = None
        self._fused = None
        self._params_cache: Dict[int, object] = {}

    def _program(self, make, tokens_spec, extra_in=(), n_groups=1):
        """A decode-side program shard_map'd over the mesh: the full
        masked state, the (replicated) tokens and ``extra_in`` in;
        ``tokens_spec`` and ``n_groups`` write groups out."""
        full_state = {"k_pages": PAGE_SPEC, "v_pages": PAGE_SPEC,
                      "page_tables": P(), "lengths": P()}
        return jax.jit(jax.shard_map(
            make(self._local, tp_axis=SERVE_TP_AXIS), mesh=self.mesh,
            in_specs=(self._pspecs, full_state, P()) + extra_in,
            out_specs=(tokens_spec, (_WRITE_SPECS,) * n_groups),
            check_vma=False))

    @property
    def verify(self):
        if self._verify is None:
            self._verify = self._program(make_verify_step, P())
        return self._verify

    @property
    def fused(self):
        # the fused step's decode half takes the full masked state, its
        # prefill half the same replicated control metadata as chunk;
        # outputs are (replicated tokens, both halves' writes) — so the
        # specs are exactly the union of decode's and chunk's
        if self._fused is None:
            self._fused = self._program(make_fused_step, (P(), P()),
                                        extra_in=(P(),) * 4, n_groups=2)
        return self._fused

    def prepare_params(self, params, device=None):
        """device_put ``params`` into the TP layout on the mesh (cached
        by object identity so router replicas sharing one params tree
        also share one sharded copy; the original is kept referenced so
        a recycled id can never alias a dead tree).  The mesh places
        the engine, so ``device`` is ignored."""
        key = id(params)
        if key not in self._params_cache:
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self._pspecs,
                is_leaf=lambda x: isinstance(x, P))
            self._params_cache[key] = (
                params, jax.device_put(params, shardings))
        return self._params_cache[key][1]

    def page_sharding(self, device=None):
        return NamedSharding(self.mesh, PAGE_SPEC)

    def index_sharding(self, device=None):
        """Where a step's write targets live: replicated."""
        return NamedSharding(self.mesh, P())
