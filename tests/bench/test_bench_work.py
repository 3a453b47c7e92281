"""The operation and byte counters behind ``step_roofline`` and
``step.mfu`` (the dense GQA module's, ``bench/arch/dense_gqa.py``), the
peaks table, and the configuration files, each against numbers worked
out by hand."""
import json

import pytest

from benchkit import REPO, SMOKE_CONFIG
from bench.lib import harness, spec, work

dense = spec.load_arch(REPO / "bench/arch/dense_gqa.py")
K = dense.dims(SMOKE_CONFIG)     # L=2 d=64 H=4 KVH=2 Dh=16 F=128 V=256


def test_smoke_dims():
    assert (K.L, K.d, K.H, K.KVH, K.Dh, K.F, K.V) == (2, 64, 4, 2, 16, 128,
                                                      256)
    assert K.kv_bytes_per_token == 2 * 2 * 2 * 16 * 2          # 256


def test_per_token_counts_by_hand():
    # q 64x64, k and v 64x32 each, o 64x64, mlp 3 x 64x128, two layers
    assert dense.linear_flops(K) == 2 * 2 * (4096 + 2048 + 2048 + 4096
                                             + 3 * 8192)       # 147456
    assert dense.attention_flops(K, 10) == 4 * 2 * 4 * 16 * 10  # 5120
    assert dense.unembed_flops(K) == 2 * 64 * 256              # 32768


def test_decode_row_by_hand():
    w = dense.decode_rows(K, [9])
    assert w.flops == 147456 + 32768 + 5120      # 10 keys for the new token
    assert w.bytes == 10 * 256                   # read 9, write 1
    w2 = dense.decode_rows(K, [9], T=2)           # keys 10 + 11
    assert w2.flops == 2 * (147456 + 32768) + 4 * 2 * 4 * 16 * 21
    assert w2.bytes == 11 * 256


def test_prefill_row_by_hand():
    w = dense.prefill_rows(K, [16, 0], [8, 0])    # second row is padding
    keys = sum(16 + t + 1 for t in range(8))     # 164
    assert keys == 164
    assert w.flops == 8 * 147456 + 4 * 2 * 4 * 16 * keys + 32768
    assert w.bytes == (16 + 8) * 256


def test_weight_bytes_by_hand():
    layers = 2 * (2 * 64          # ln1, ln2 (stacked, so bf16)
                  + 64 * 64 * 2 + 64 * 32 * 2 + 2 * 16    # q, o, k, v, qk norm
                  + 3 * 64 * 128)
    want = 256 * 64 * 2 + 64 * 4 + layers * 2
    assert dense.weight_bytes(K) == want                       # 181120


def test_a_dispatch_reads_the_weights_once_by_hand():
    wb = dense.weight_bytes(K)
    d = dense.dispatch_work(K, decode=([9], 1))
    assert (d.flops, d.bytes, d.weight_bytes) == (147456 + 32768 + 5120,
                                                  10 * 256, wb)
    c = dense.dispatch_work(K, prefill=([16, 0], [8, 0]))
    assert (c.flops, c.bytes, c.weight_bytes) == (
        8 * 147456 + 4 * 2 * 4 * 16 * 164 + 32768, 24 * 256, wb)
    # a fused dispatch: both kinds of rows, and the weights once
    f = dense.dispatch_work(K, decode=([9], 1), prefill=([16, 0], [8, 0]))
    assert (f.flops, f.bytes, f.weight_bytes) == (d.flops + c.flops,
                                                  d.bytes + c.bytes, wb)


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert work.least_seconds(work.Work(flops=1000.0, bytes=50.0),
                              peak) == 10.0                    # compute
    assert work.least_seconds(work.Work(flops=1000.0, bytes=50.0,
                                        weight_bytes=200), peak) == 25.0


def test_peaks_table():
    table = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


@pytest.mark.parametrize("name,arch,kv_bytes", [
    ("qwen3-0.6b", "qwen3-0.6b", 112 * 1024),
    ("qwen2-0.5b", "qwen2-0.5b", 12 * 1024)])
def test_configuration_files_match_the_published_widths(name, arch, kv_bytes):
    from repro import configs
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    mod = spec.load_arch(REPO / cfg["reference"])
    k = mod.dims(cfg)
    assert k.kv_bytes_per_token == kv_bytes
    mine, zoo = mod.program_config(cfg), configs.get(arch)
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "qkv_bias", "qk_norm", "rope_theta",
              "tie_embeddings"):
        assert getattr(mine, f) == getattr(zoo, f), f
    assert mine.param_dtype == "bfloat16"
    assert cfg["reduced"] == []
