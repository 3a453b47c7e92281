"""The phase and scope reader (``bench/lib/phases.py``): idle time charged
to the innermost host span, op scope paths read from the XSpace proto's
wire format, the numbers read from them; on hand-made traces, on one
recorded on a TPU v5e, on a CPU trace of the serve engine, and through
``bench/trace_phases.py`` at a smoke size."""
import gzip
import importlib.util
import json
import pathlib
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

import benchkit
from benchkit import REPO
from bench.lib import phases

CHIP_TRACE = REPO / "bench" / "testdata" / "v5e_chat_long.xplane.pb.gz"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


ATTN = "jit(fused_step)/while/body/closed_call/attn/attn.core/dot_general"
SLICE = "jit(fused_step)/while/body/dynamic_slice"


def profile():
    """One chip busy 100-300 and 900-1000 ns (two step-program runs: an
    attention op and the loop's slice of the pool) in a window 0-1000;
    nested harness and engine spans on the host."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_fused_step(3)", 100, 200),
                                       ev("jit_fused_step(3)", 900, 100)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 100, 100),
                                   ev("gather.2", 200, 100),
                                   ev("gather.2", 900, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced", 0, 1000), ev("bench.step", 50, 900),
        ev("serve.step", 60, 880), ev("serve.dispatch", 70, 50),
        ev("serve.wait", 120, 180), ev("serve.bank", 300, 100),
        ev("serve.admit", 400, 200), ev("serve.headroom", 450, 50),
        ev("bench.events", 950, 50), ev("$scheduler.py:754", 0, 5)])])
    return NS(planes=[host, device])


SCOPES = {"/device:TPU:0": {"fusion.1": ATTN, "gather.2": SLICE}}


def test_innermost_names_each_piece_of_the_time_line():
    pieces = phases.innermost([("a", 0, 10), ("b", 2, 6), ("c", 3, 4),
                               ("d", 12, 14)])
    assert pieces == [("a", 0, 2), ("b", 2, 3), ("c", 3, 4), ("b", 4, 6),
                      ("a", 6, 10), ("d", 12, 14)]


def test_idle_time_goes_to_the_innermost_span():
    ph = phases.reduce_profile(profile(), SCOPES)
    idle = ph.idle_by_span()
    want = {"host": 50, "bench.step": 10, "serve.step": 10 + 300,
            "serve.dispatch": 30, "serve.bank": 100, "serve.admit": 150,
            "serve.headroom": 50}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # every idle instant is charged once
    assert sum(idle.values()) == pytest.approx(
        ph.reduced.window_s - ph.reduced.busy_s)
    assert ph.top_gaps() == [["serve.step", pytest.approx(600e-9)],
                             ["serve.dispatch", pytest.approx(100e-9)]]


def test_the_four_numbers_on_a_hand_made_trace():
    ph = phases.reduce_profile(profile(), SCOPES)
    assert ph.executions == 2
    got = {k: f(ph) for k, f in phases.METRICS.items()}
    assert got == pytest.approx({
        # one step less its dispatch and its wait
        "sched.host_ms": (880 - 50 - 180) * 1e-6,
        "runtime.dispatch_ms": 50 * 1e-6,
        "kv.headroom_ms": 50 * 1e-6,
        "step.attn_ms": 100e-9 / 2 * 1e3,
        "step.scan_io_ms": 200e-9 / 2 * 1e3,
        "step.layout_copy_ms": 0.0})
    assert ph.by_scope() == pytest.approx({"attn": 100e-9,
                                           "while": 200e-9})
    assert ph.scope_seconds("attn.core") == pytest.approx(100e-9)


def test_an_op_with_no_path_counts_at_its_programs_root():
    # the compiler's own ops (layout copies) carry no scope path
    ph = phases.reduce_profile(profile(), {"/device:TPU:0": {
        "fusion.1": ATTN}})
    assert ph.scoped == pytest.approx({ATTN: 100e-9,
                                       "jit(fused_step)/": 200e-9})
    assert ph.by_scope() == pytest.approx({"attn": 100e-9,
                                           "other": 200e-9})
    assert phases.step_scan_io_ms(ph) is None


def test_a_layout_copy_outside_the_loop_is_named():
    prof = profile()
    prof.planes[1].lines[1].events[1] = ev("%copy.3 = bf16[8]", 200, 100)
    ph = phases.reduce_profile(prof, SCOPES)
    assert ph.by_scope() == pytest.approx({"attn": 100e-9, "copy": 100e-9,
                                           "while": 100e-9})
    assert phases.step_layout_copy_ms(ph) == pytest.approx(100e-9 / 2 * 1e3)
    assert phases.step_scan_io_ms(ph) == pytest.approx(100e-9 / 2 * 1e3)
    assert [phases._is_copy(n) for n in (
        "%copy.89 = bf16[2]{0} copy(bf16[2]{0} %bitcast.23)",
        "copy-start.1", "%fusion.11 = bf16[2] fusion(%copy.60)",
        "copy_fusion.2")] == [True, True, False, False]


def test_no_spans_and_no_scopes_read_as_nothing():
    prof = profile()
    prof.planes[0].lines[0].events = [ev("bench.traced", 0, 1000)]
    ph = phases.reduce_profile(prof, {"/device:TPU:0": {
        "fusion.1": "jit(fused_step)/while/body/dot_general",
        "gather.2": SLICE}})
    assert all(f(ph) is None for f in phases.METRICS.values())
    assert ph.by_scope() == {}
    assert ph.idle_by_span() == pytest.approx({"host": 700e-9})
    assert [g[0] for g in ph.top_gaps()] == ["host", "host"]


# ------------------------------------------------ XSpace wire format
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _entry(key, value):
    return _msg((1, key), (2, value))


def _op(i, name, *stats):
    return _entry(i, _msg((1, i), (2, name), *[(5, s) for s in stats]))


def test_op_scopes_reads_the_event_metadata_stats():
    tf_op, flops, ref = 26, 27, 40
    stat_meta = [(5, _entry(k, _msg((1, k), (2, n)))) for k, n in
                 ((tf_op, "tf_op"), (flops, "flops"),
                  (ref, "jit(g)/while/body/mlp/dot:"))]
    device = _msg((1, 7), (2, "/device:TPU:0"),
                  (3, _msg((2, "XLA Ops"))),           # lines: skipped
                  (4, _op(1, "%fusion.1 = f32[8]", _msg((1, flops), (4, 9)),
                          _msg((1, tf_op), (5, "jit(g)/attn/add:")))),
                  (4, _op(2, "%dot.2", _msg((1, tf_op), (7, ref)))),
                  (4, _op(3, "%copy.3", _msg((1, flops), (4, 1)))),
                  (4, _op(4, "%dup", _msg((1, tf_op), (5, "jit(g)/a:")))),
                  (4, _op(5, "%dup", _msg((1, tf_op), (5, "jit(g)/b:")))),
                  *stat_meta)
    host = _msg((2, "/host:CPU"), *stat_meta,
                (4, _op(1, "bench.step", _msg((1, tf_op), (5, "x")))))
    space = _msg((1, host), (1, device), (4, "hostname"))
    assert phases.op_scopes(space) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(g)/attn/add",
        "%dot.2": "jit(g)/while/body/mlp/dot",
        "%dup": None}}


def test_a_chip_trace_reads_as_before():
    """Recorded on the parent of the engine's spans and the model's
    scopes: the gaps keep the names the busy/idle reduction gives them,
    the op paths are there, and the four numbers read nothing."""
    ph = phases.load(str(CHIP_TRACE))
    assert ph.top_gaps() == ph.reduced.top_gaps()
    assert sum(ph.idle_by_span().values()) == pytest.approx(
        ph.reduced.window_s - ph.reduced.busy_s)
    assert all(f(ph) is None for f in phases.METRICS.values())
    assert ph.executions == ph.reduced.module_seconds(
        ("jit_fused_step",))[0] > 0
    with open(CHIP_TRACE, "rb") as f:
        paths = set(phases.op_scopes(gzip.decompress(f.read()))[
            "/device:TPU:0"].values())
    assert "jit(fused_step)/while/body/dynamic_slice" in paths
    # the step programs' ops are all read with a path
    assert sum(ph.scoped.values()) > 0.9 * ph.reduced.module_seconds(
        ("jit_fused_step",))[1]


# ----------------------------------------- the engine under a profiler
def _engine_run(bundle, profile_dir=None):
    from repro.serve import Request, ServeEngine
    cfg, model, params, programs = bundle
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in (30, 28, 18, 9)]
    # 13 pages of 8 for three rows of ~38 tokens: preemption and replay
    eng = ServeEngine(model, params, max_batch=3, n_pages=13, page_size=8,
                      max_pages_per_seq=8, chunk_size=16, prefill_batch=2,
                      programs=programs)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    n = 0
    if profile_dir is None:
        while eng.step():
            n += 1
    else:
        with jax.profiler.trace(str(profile_dir)), \
                jax.profiler.TraceAnnotation("bench.traced"):
            while eng.step():
                n += 1
    return eng, {r.rid: list(r.generated) for r in reqs}, n + 1


@pytest.fixture(scope="module")
def bundle():
    from repro import configs
    from repro.models import build_model
    from repro.serve.step import ServePrograms
    cfg = configs.get_smoke("qwen3-0.6b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0)), \
        ServePrograms(model)


def test_engine_phases_nest_in_each_step(bundle, tmp_path):
    _engine_run(bundle)                                    # compiles
    eng, tokens, n_steps = _engine_run(bundle, tmp_path)
    st = eng.stats()
    assert st["n_replay_steps"] >= 1 and st["n_fused_dispatches"] >= 1
    xp = list(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    spans = [s for s in phases.load(str(xp[0])).spans
             if s[0].startswith("serve.")]
    steps = [(a, b) for n, a, b in spans if n == "serve.step"]
    assert len(steps) == n_steps
    inside = {i: [] for i in range(len(steps))}
    for name, a, b in spans:
        if name == "serve.step":
            continue
        home = [i for i, (s0, s1) in enumerate(steps) if s0 <= a and b <= s1]
        assert len(home) == 1, (name, a, b)       # no phase outside a step
        inside[home[0]].append(name)
    # each dispatch of a step program is waited on, in order; a replay
    # dispatch is not
    n_dispatch = 0
    for names in inside.values():
        seq = [n for n in names if n in ("serve.dispatch", "serve.wait")]
        assert seq == ["serve.dispatch", "serve.wait"] * (len(seq) // 2)
        n_dispatch += len(seq) // 2
    assert n_dispatch == st["n_total_dispatches"] - st["n_replay_steps"]
    names = [n for ns in inside.values() for n in ns]
    assert names.count("serve.replay") == st["n_replay_steps"]
    assert {"serve.admit", "serve.headroom", "serve.inputs",
            "serve.bank"} <= set(names)
    # the profiler session changes no token
    assert tokens == _engine_run(bundle)[1]


# ------------------------------------------------ the tool at smoke size
def _tool():
    spec = importlib.util.spec_from_file_location(
        "trace_phases", REPO / "bench" / "trace_phases.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_key_flag():
    # the tool puts HLO metadata in the compile cache's key, process-wide
    flag = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, flag)
    yield
    jax.config.update(flag, old)


def test_trace_phases_on_the_cpu(tmp_path_factory, monkeypatch, capsys,
                                 cache_key_flag):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")
    benchkit.smoke_sizes(monkeypatch)
    root = benchkit.make_root(tmp_path_factory.mktemp("checkout"))
    keep = tmp_path_factory.mktemp("keep")
    tool = _tool()
    argv = ["--workload", "smoke.chat", "--seed", str(2**40 + 7),
            "--seconds", "4"]
    assert tool.main(argv, root=root) == 2            # no accelerator
    rc = tool.main(argv + ["--keep", str(keep)], require_accelerator=False,
                   root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    m = res["metrics"]
    # the CPU trace has host spans and no device plane
    assert m["sched.host_ms"] > 0 and m["kv.headroom_ms"] >= 0
    assert m["runtime.dispatch_ms"] > 0
    assert m["step.attn_ms"] is None and m["step.device_ms"] is None
    assert res["cow_per_s"] >= 0
    assert res["tpot_p50_ms"] > 0
    assert list(keep.glob("smoke.chat.*.xplane.pb.gz"))
