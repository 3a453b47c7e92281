"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (a block
not tiled (8, 128), too much VMEM), and a CPU run never sees whether a
program fits the chip's HBM.  These tests compile the main serve path
for v5e with the installed libtpu: the kernels at qwen3-0.6b's widths,
the full-width fused step on one chip (at the chip smoke's sizes and at
the chat-long cell's) and under TP=4 on the ``v5e:2x2`` mesh, and the
page writer that stores each step's K/V into the donated pools.
Nothing runs; a passing compile is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and every xdist worker imports this
file.  Keep every such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs
from repro.models import build_model

#: HBM of one v5e as its compiler reports it ("Used ... of 15.75G hbm")
V5E_HBM_BYTES = 15.75 * 2**30

# the chip smoke's serve phase: batch 8, page size 16, 2,048 pages,
# speculation k=4 (verify T=5), chunk 32, 256+64-token requests
BATCH, PAGE, N_PAGES, T_VERIFY, CHUNK = 8, 16, 2048, 5, 32
MAX_PAGES_PER_SEQ = (256 + 64) // PAGE
SMOKE = dict(n_pages=N_PAGES, batch=BATCH, width=T_VERIFY, p_batch=BATCH,
             chunk=CHUNK, bucket=MAX_PAGES_PER_SEQ, mpps=MAX_PAGES_PER_SEQ)
# the qwen3-0.6b.chat-long cell (bench/cells): 3,712 pages, batch 32,
# decode T=1, prefill 4 x 256 at the 256-page bucket, tables of
# pages_needed(4,096 + 512) = 288 pages
CHAT_LONG = dict(n_pages=3712, batch=32, width=1, p_batch=4, chunk=256,
                 bucket=256, mpps=288)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    # a TPU executable written to the persistent cache cannot be read
    # back without the chip: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def qwen3():
    return build_model(configs.get("qwen3-0.6b"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _fused_args(model, params_sh, page_sh, rep_sh, params, *, n_pages,
                batch, width, p_batch, chunk, bucket, mpps, shards=1):
    """Abstract arguments of ``programs.fused`` at the given sizes,
    the pools in the engine's own layout for ``shards`` devices."""
    pages = model.paged_state_specs(batch, n_pages=n_pages, page_size=PAGE,
                                    max_pages_per_seq=mpps,
                                    shards=shards)["k_pages"].shape
    i32 = jnp.int32
    state = {"k_pages": _sds(pages, jnp.bfloat16, page_sh),
             "v_pages": _sds(pages, jnp.bfloat16, page_sh),
             "page_tables": _sds((batch, mpps), i32, rep_sh),
             "lengths": _sds((batch,), i32, rep_sh)}
    abstract = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), params, params_sh)
    return (abstract, state,
            _sds((batch, width), i32, rep_sh),
            _sds((p_batch, chunk), i32, rep_sh),
            _sds((p_batch, bucket), i32, rep_sh),
            _sds((p_batch,), i32, rep_sh), _sds((p_batch,), i32, rep_sh))


def _nbytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _pool_relayouts(text: str, pool_shape) -> list:
    """The compiled program's copies and transposes of a page pool, whole
    or one layer of it: those whose result has the pool's page axis."""
    L, n_pages = pool_shape[:2]
    op = re.compile(r"= bf16\[([\d,]+)\]\S* (?:copy|transpose)\(")
    out = []
    for line in text.splitlines():
        m = op.search(line)
        if m and {n_pages, L * n_pages} & {int(d) for d in
                                          m.group(1).split(",")}:
            out.append(line.strip())
    return out


@pytest.mark.parametrize("kvh", [8, 2])
def test_paged_attention_compiles(one_chip, kvh):
    """qwen3 decode widths: G=2, Dh=128, page 16, 64 slots; KVH=2 is
    the TP=4 shard-local view."""
    from repro.kernels.paged_attention import paged_attention
    B, G, Dh, n_slots = BATCH, 2, 128, 64
    P_ = B * n_slots + 1
    pages = _sds((P_, PAGE, kvh, Dh), jnp.bfloat16, one_chip)
    c = jax.jit(paged_attention).lower(
        _sds((B, kvh * G, Dh), jnp.bfloat16, one_chip), pages, pages,
        _sds((B, n_slots), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import ops as fa
    S, H, KVH, Dh = 512, 16, 8, 128
    c = jax.jit(fa.attention).lower(
        _sds((1, S, H, Dh), jnp.bfloat16, one_chip),
        _sds((1, S, KVH, Dh), jnp.bfloat16, one_chip),
        _sds((1, S, KVH, Dh), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_fused_step_fits_one_chip(one_chip, qwen3):
    """The full-width fused step at the smoke's 2,048-page pool fits
    the chip: the page state is an argument only (it reads the pools
    in place and returns the step's writes), so the pool counts
    once."""
    from repro.serve.step import ServePrograms
    params = qwen3.abstract()
    params_sh = jax.tree.map(lambda _: one_chip, params)
    args = _fused_args(qwen3, params_sh, one_chip, one_chip, params,
                       **SMOKE)
    c = ServePrograms(qwen3).fused.lower(*args).compile()
    assert _footprint(c) < V5E_HBM_BYTES, c.memory_analysis()


def test_fused_step_holds_no_pool_at_chat_long_sizes(one_chip, qwen3):
    """At the chat-long cell's sizes the fused step returns its tokens
    and the step's new K/V with their targets and nothing else (1,024
    prefill tokens' K/V are 112 MiB), and it neither slices nor
    restacks nor copies a pool: its temporaries are the pages it
    gathers — the decode rows' K and V with one attention-layout copy
    of each, the prefill rows' K and V — and no more (v5e AOT: 1.107
    GiB of a 1.188 GiB bound; 1.85 GiB with the pools carried through
    the layer loop)."""
    from repro.serve.step import ServePrograms
    params = qwen3.abstract()
    params_sh = jax.tree.map(lambda _: one_chip, params)
    args = _fused_args(qwen3, params_sh, one_chip, one_chip, params,
                       **CHAT_LONG)
    fused = ServePrograms(qwen3).fused
    tokens, writes = jax.eval_shape(fused, *args)
    c = fused.lower(*args).compile()
    m = c.memory_analysis()
    assert m.output_size_in_bytes <= _nbytes((tokens, writes)) + 2**20, m
    assert m.output_size_in_bytes < 128 * 2**20, m
    pool = args[1]["k_pages"].shape
    token = int(np.prod(pool[3:])) * 2 * PAGE       # a page's bytes
    gathered = (CHAT_LONG["batch"] * CHAT_LONG["mpps"] * 4
                + CHAT_LONG["p_batch"] * CHAT_LONG["bucket"] * 2) * token
    assert m.temp_size_in_bytes < gathered, (m, gathered)
    assert not _pool_relayouts(c.as_text(), pool)


@pytest.mark.parametrize("chips", [1, 4])
def test_page_writer_stores_in_place(topo, qwen3, chips):
    """The writer of the chat-long cell's fused step updates both pools
    in place: its outputs alias both donated pools, it needs no
    temporary beyond its own indices, and on four chips each shard
    scatters its own heads with no collective."""
    from repro.serve.parallel import (PAGE_SPEC, TPServePrograms,
                                      tp_param_specs)
    from repro.serve.step import ServePrograms
    params = qwen3.abstract()
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        progs = ServePrograms(qwen3)
        params_sh = jax.tree.map(lambda _: one, params)
        page_sh = rep_sh = one
    else:
        mesh = Mesh(np.asarray(topo.devices), ("tp",))
        progs = TPServePrograms(qwen3, mesh=mesh)
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 tp_param_specs(qwen3),
                                 is_leaf=lambda x: isinstance(x, P))
        page_sh = NamedSharding(mesh, PAGE_SPEC)
        rep_sh = NamedSharding(mesh, P())
    args = _fused_args(qwen3, params_sh, page_sh, rep_sh, params,
                       shards=chips, **CHAT_LONG)
    _, writes = jax.eval_shape(progs.fused, *args)
    writes = jax.tree.map(lambda a: _sds(a.shape, a.dtype, a.sharding),
                          writes)
    pool = args[1]["k_pages"]
    c = progs.write_pages.lower(pool, pool, writes).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes == 2 * _nbytes(pool) // chips, m
    assert m.temp_size_in_bytes < 2**20, m
    text = c.as_text()
    for collective in ("all-gather", "all-to-all", "collective-permute",
                       "all-reduce"):
        assert collective not in text, collective


def test_tp4_fused_step_compiles(topo, qwen3):
    """The fused step shard_map'd over the four chips of a v5e:2x2
    host: heads, FFN and pages split four ways, the rest replicated.
    Each shard's pool is laid out for its own two heads (one 256-wide
    row a token), so the chip keeps it row-major and the step copies
    no pool (with (2, 128) slabs: two whole-pool copies a step and
    1.19 GiB of temporaries)."""
    from repro.serve.parallel import (PAGE_SPEC, TPServePrograms,
                                      tp_param_specs)
    mesh = Mesh(np.asarray(topo.devices), ("tp",))
    progs = TPServePrograms(qwen3, mesh=mesh)
    params = qwen3.abstract()
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             tp_param_specs(qwen3),
                             is_leaf=lambda x: isinstance(x, P))
    args = _fused_args(qwen3, params_sh, NamedSharding(mesh, PAGE_SPEC),
                       NamedSharding(mesh, P()), params, shards=4, **SMOKE)
    c = progs.fused.lower(*args).compile()
    text = c.as_text()
    assert "all-gather" in text
    assert _footprint(c) < V5E_HBM_BYTES, c.memory_analysis()
    pool = args[1]["k_pages"]
    assert not _pool_relayouts(text, pool.shape)
    assert c.memory_analysis().temp_size_in_bytes < _nbytes(pool) / 4
