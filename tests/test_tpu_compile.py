"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (a block
not tiled (8, 128), too much VMEM), and a CPU run never sees whether a
program fits the chip's HBM.  These tests compile the main serve path
for v5e with the installed libtpu: the kernels at qwen3-0.6b's widths,
and the full-width fused step on one chip and under TP=4 on the
``v5e:2x2`` mesh.  Nothing runs; a passing compile is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and every xdist worker imports this
file.  Keep every such compile in this one file.
"""
import os

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


def _fused_args(cfg, params_sh, page_sh, rep_sh, params):
    """Abstract arguments of ``programs.fused`` at the smoke sizes."""
    L, KVH, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    pages = (L, N_PAGES, PAGE, KVH, Dh)
    i32 = jnp.int32
    state = {"k_pages": _sds(pages, jnp.bfloat16, page_sh),
             "v_pages": _sds(pages, jnp.bfloat16, page_sh),
             "page_tables": _sds((BATCH, MAX_PAGES_PER_SEQ), i32, rep_sh),
             "lengths": _sds((BATCH,), i32, rep_sh)}
    abstract = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), params, params_sh)
    return (abstract, state,
            _sds((BATCH, T_VERIFY), i32, rep_sh),
            _sds((BATCH, CHUNK), i32, rep_sh),
            _sds((BATCH, MAX_PAGES_PER_SEQ), i32, rep_sh),
            _sds((BATCH,), i32, rep_sh), _sds((BATCH,), i32, rep_sh))


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
    the chip: the page state is an argument and an output (not
    donated), so the pool counts twice."""
    from repro.serve.step import ServePrograms
    params = qwen3.abstract()
    params_sh = jax.tree.map(lambda _: one_chip, params)
    args = _fused_args(qwen3.cfg, params_sh, one_chip, one_chip, params)
    c = ServePrograms(qwen3).fused.lower(*args).compile()
    assert _footprint(c) < V5E_HBM_BYTES, c.memory_analysis()


def test_tp4_fused_step_compiles(topo, qwen3):
    """The fused step shard_map'd over the four chips of a v5e:2x2
    host: heads, FFN and pages split four ways, the rest replicated."""
    from repro.serve.parallel import (PAGE_SPEC, TPServePrograms,
                                      tp_param_specs)
    mesh = Mesh(np.asarray(topo.devices), ("tp",))
    progs = TPServePrograms(qwen3, mesh=mesh)
    params = qwen3.abstract()
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             tp_param_specs(qwen3),
                             is_leaf=lambda x: isinstance(x, P))
    args = _fused_args(qwen3.cfg, params_sh, NamedSharding(mesh, PAGE_SPEC),
                       NamedSharding(mesh, P()), params)
    c = progs.fused.lower(*args).compile()
    text = c.as_text()
    assert "all-gather" in text
    assert _footprint(c) < V5E_HBM_BYTES, c.memory_analysis()
