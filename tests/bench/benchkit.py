"""A small checkout for the benchmark's CPU tests: the repo's ``bench/``
tree beside a ``BENCHMARK.json`` whose cells are smoke-sized, so the
harness runs end to end on the CPU in seconds.  A test may place an
architecture module of its own in the checkout's ``bench/arch/``."""
from __future__ import annotations

import copy
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

#: qwen3-shaped, 2 layers of width 64 (qk-norm, tied table, GQA 4/2)
SMOKE_CONFIG = {
    "name": "smoke-dense", "source": "tests/bench/benchkit.py",
    "dtype": "bfloat16", "reference": "bench/arch/dense_gqa.py",
    "arch": {"qk_norm": True, "attention_bias": False},
    "program": {"attn_kv_chunk": 16},
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "tie_word_embeddings": True},
    "reduced": [],
}

#: the same shape four layers deep at width 256 and a vocabulary of
#: 4,096: the smallest size at which int8 rounding stands clear of the
#: program's bf16 noise (CPU, seeds 100-107: the program reads at most
#: 0.0085, the int8 control at least 0.026); at width 64 the two overlap
CONTROL_CONFIG = dict(SMOKE_CONFIG, name="control-dense", model=dict(
    SMOKE_CONFIG["model"], hidden_size=256, intermediate_size=768,
    head_dim=64, num_hidden_layers=4, vocab_size=4096))

SMOKE_TRAFFIC = {
    "kind": "requests", "arrivals": {"process": "poisson"},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
               "min": 8, "max": 48},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
               "min": 8, "max": 24},
}

SMOKE_CELL = {
    "serve": {"batch": 4, "prefill_batch": 2, "chunk_size": 16,
              "page_size": 8, "n_pages": 48, "bucket_edges": [2, 4, 8],
              "spec_k": 0},
    "load": {"mode": "open", "rate_rps": 4.0, "warm_s": 1.0},
    "drain_cap_s": 120,
    # the program reads at most 0.0049 here (CPU, seeds 100-111)
    "check": {"max_logit_gap": 0.02, "control": "int8"},
}


def smoke_sizes(monkeypatch) -> None:
    """The harness's trace and sample sizes, cut to the smoke cells'
    seconds-long windows and 16-token outputs."""
    from bench.lib import harness
    for name, value in (("TRACE_START_FRAC", 0.3), ("TRACE_SECONDS", 1.0),
                        ("CHECK_TOKENS", 200), ("CHECK_MAX_REQUESTS", 16),
                        ("CHECK_MIN_TOKENS", 100)):
        monkeypatch.setattr(harness, name, value)


#: a made-up device for CPU runs of the traced path
CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}


def make_root(tmp: pathlib.Path, *, cells=None, config=SMOKE_CONFIG,
              arch=None) -> pathlib.Path:
    """``tmp`` as a checkout: ``bench/`` copied, plus the smoke config
    (named ``smoke-dense``), the smoke traffic mix and a cell per entry
    of ``cells`` (name -> cell dict), listed in a fresh
    ``BENCHMARK.json``; ``arch``, a module file, is copied to
    ``bench/arch/`` under its own name."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if arch is not None:
        shutil.copy(arch, tmp / "bench/arch" / pathlib.Path(arch).name)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = cells or {"smoke.chat": SMOKE_CELL}
    (tmp / "bench/configs/smoke-dense.json").write_text(
        json.dumps(dict(config, name="smoke-dense")))
    (tmp / "bench/traffic/smoke.json").write_text(json.dumps(SMOKE_TRAFFIC))
    bench["configs"].append({"name": "smoke-dense", "source": "test",
                             "file": "bench/configs/smoke-dense.json",
                             "reduced": [], "why": "test"})
    for name, cell in cells.items():
        (tmp / f"bench/cells/{name}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": name, "config": "smoke-dense",
                                   "traffic": "smoke", "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and name not in m["workloads"]:
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cell(**over):
    c = copy.deepcopy(SMOKE_CELL)
    for k, v in over.items():
        c[k] = v
    return c
