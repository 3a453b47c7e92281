"""The serve engine measured from inside: the model's named scopes in the
step programs' HLO, the first-token latency that counts the step that
made the token, and a telemetry module that imports without jax.

(The engine's phase spans under a profiler session, reduced from the
trace, are tested beside the trace reader in
tests/bench/test_bench_phases.py.)"""
import json
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model
from repro.serve import Request, ServeEngine, ServeFrontend
from repro.serve.step import ServePrograms

REPO = Path(__file__).resolve().parent.parent
TELEMETRY = REPO / "src" / "repro" / "serve" / "telemetry.py"
B, BP, CHUNK, NB = 4, 2, 16, 4


@pytest.fixture(scope="module")
def bundle():
    cfg = configs.get_smoke("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, ServePrograms(model)


def _lowered(model, params, programs, kind: str):
    specs = model.paged_state_specs(B, n_pages=32, page_size=8,
                                    max_pages_per_seq=8)
    state = {k: jnp.zeros(v.shape, v.dtype) for k, v in specs.items()}
    z, zp = jnp.zeros((BP,), jnp.int32), jnp.zeros((BP, NB), jnp.int32)
    chunk = jnp.zeros((BP, CHUNK), jnp.int32)
    if kind == "chunk":
        pages = {k: state[k] for k in ("k_pages", "v_pages")}
        return programs.chunk.lower(params, pages, chunk, zp, z, z)
    if kind == "fused":
        return programs.fused.lower(params, state,
                                    jnp.zeros((B, 1), jnp.int32), chunk,
                                    zp, z, z)
    prog = programs.decode if kind == "decode" else programs.verify
    width = 1 if kind == "decode" else 3
    return prog.lower(params, state, jnp.zeros((B, width), jnp.int32))


#: every scope a program's ops fall under; the chunk scatter follows the
#: layer scan only where prefill rows are written
SCOPES = ["embed", "norm", "attn", "attn.gather", "attn.core", "mlp",
          "unembed", "sample"]


@pytest.mark.parametrize("kind,extra", [
    ("decode", ["attn.kv_write"]), ("verify", ["attn.kv_write"]),
    ("chunk", ["kv_scatter"]), ("fused", ["attn.kv_write", "kv_scatter"])])
def test_step_programs_carry_the_model_scopes(bundle, kind, extra):
    _, model, params, programs = bundle
    hlo = _lowered(model, params, programs, kind).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    root = {"decode": "paged_step"}.get(kind, f"{kind}_step")
    assert any(p.startswith(f"jit({root})/while/body/") for p in paths)
    parts = {s for p in paths for s in p.split("/")}
    for scope in SCOPES + extra:
        assert scope in parts, (kind, scope)
    # the attention's children sit under ``attn``
    assert any("/attn/attn.gather/" in p for p in paths)
    assert any("/attn/attn.core/" in p for p in paths)
    # in the layer loop, only the scan's own slicing and stacking (and
    # its counter) fall under no model scope
    loose = {p.split("/while/body/", 1)[1].split("/")[0] for p in paths
             if "/while/body/" in p
             and not set(p.split("/")) & set(SCOPES + extra)}
    assert loose <= {"dynamic_slice", "dynamic_update_slice", "add",
                     "closed_call", "squeeze"}, loose


def _engine(bundle):
    cfg, model, params, programs = bundle
    return ServeEngine(model, params, max_batch=2, n_pages=16,
                       page_size=8, max_pages_per_seq=8, chunk_size=CHUNK,
                       programs=programs)


PROMPT = np.arange(1, 9, dtype=np.int32)                 # one chunk


def test_ttft_counts_the_step_that_made_the_token(bundle):
    eng = _engine(bundle)
    warm = Request(rid=0, prompt=PROMPT, max_new_tokens=1, arrival=0.0)
    eng.submit(warm)
    while eng.step(now=0.0):                         # compiles
        pass
    req = Request(rid=1, prompt=PROMPT, max_new_tokens=4, arrival=5.0)
    eng.submit(req)
    t = time.perf_counter()

    def clock():                          # wall seconds, 5.0 at ``t``
        return 5.0 + time.perf_counter() - t
    eng.step(now=5.0, clock=clock)   # admits, prefills, promotes, decodes
    took = time.perf_counter() - t
    assert req.generated
    # the clock read 5.0 as the step began; the token exists only once
    # the step banked it
    assert 0.0 < req.ttft <= took
    hist = eng.tel.registry.histogram("ttft", tenant="default",
                                      slo="batch")
    assert hist.samples[-1] == req.ttft
    # offline (no clock) the latency stays unknown, as before
    off = Request(rid=2, prompt=PROMPT, max_new_tokens=1)
    eng.submit(off)
    while eng.step():
        pass
    assert off.ttft == float("inf")
    assert hist.count == 2


def test_ttft_on_a_step_clock_stays_in_whole_steps(bundle):
    """A deterministic front-end clocks the engine in pump steps and
    lends it no clock: every TTFT is a whole number of steps, however
    long a step takes on the host."""
    front = ServeFrontend(_engine(bundle))
    streams = [front.submit(PROMPT, 3) for _ in range(3)]
    while front.pump():
        pass
    ttfts = [s.req.ttft for s in streams]
    assert all(t == int(t) and 0 <= t < 10 for t in ttfts), ttfts
    assert max(ttfts) >= 1           # the third waited for a free slot


def test_telemetry_imports_without_jax():
    code = ("import sys, importlib.util as u\n"
            "sys.modules['jax'] = None\n"
            f"s = u.spec_from_file_location('tel', {str(TELEMETRY)!r})\n"
            "m = u.module_from_spec(s)\n"
            "sys.modules['tel'] = m\n"
            "s.loader.exec_module(m)\n"
            "assert callable(m.phase) and m.Telemetry\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_span_cost_script_prints_its_reading():
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "span_cost.py"),
         "--n", "2000"], check=True, timeout=120, capture_output=True,
        text=True).stdout
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ns_per_span"] >= 0 and res["spans_per_step"] == 8
    assert res["us_per_step"] == pytest.approx(res["ns_per_span"] * 8e-3)
