"""The harness end to end on the CPU at a smoke size: the result line's
shape, no result without an accelerator, a cell added as files alone,
``BENCHMARK.json`` against the contract, and the timed path broken
underneath coming out not correct."""
import json
import re

import numpy as np
import pytest

import benchkit
from benchkit import REPO
from bench.lib import harness, oracle, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # keep the CPU test runs out of the checkout's compile cache
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")
    benchkit.smoke_sizes(monkeypatch)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # smoke.busy keeps every batch row in use, so a fault in any row shows
    busy = benchkit.cell(load={"mode": "open", "rate_rps": 30.0})
    return benchkit.make_root(tmp_path_factory.mktemp("checkout"),
                              cells={"smoke.chat": benchkit.SMOKE_CELL,
                                     "smoke.busy": busy})


def _run(root, capsys, *, seed=100, trace=0, fault=None, seconds=4,
         workload="smoke.chat"):
    rc = harness.main(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        require_accelerator=False, root=root, fault=fault,
        peaks=benchkit.CPU_PEAKS)
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err


def test_no_result_without_an_accelerator(root, capsys):
    rc = harness.main(["--workload", "smoke.chat", "--seed", "1",
                       "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "platform cpu" in err and "no accelerator" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_prints_the_contract_line(root, capsys, trace):
    res, err = _run(root, capsys, trace=trace, seed=2**40 + 3)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert res["device"]["platform"] == "cpu"
    cell = spec.load_cell("smoke.chat", root=root)
    want = cell.per_layer if trace else cell.end_to_end
    names = {m.name for m in want}
    assert set(res["metrics"]) <= names
    for m in want:
        if m.name in res["metrics"]:
            assert res["metrics"][m.name]["unit"] == m.unit
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert {"device_ops", "idle_gaps"} == set(res["breakdown"])
        # the dispatch record was read
        assert res["metrics"]["sched.decode_rows_mean.chat"]["value"] >= 1
    else:
        assert set(res["metrics"]) == names
        assert all(v["value"] > 0 for v in res["metrics"].values())
    # the numbers compared, each beside its limit, end standard error
    tail = err.strip().splitlines()[-3:]
    assert [t.split(":")[0] for t in tail] == [
        "check max_logit_gap", "check bad_streams", "check sampled_tokens"]
    assert "lengths" in err and "generator late by up to" in err
    assert "as the window opened" in err


def _break(kind):
    """Faults planted in the timed path: every step program of the
    bundle is wrapped."""
    def outputs(fn, edit):
        def call(*a):
            tok, state = fn(*a)
            return edit(tok), state
        return call

    def rows(t, f):
        return tuple(f(x) for x in t) if isinstance(t, tuple) else f(t)

    def plant(name, fn):
        if kind == "state_unchanged":
            def wrapped(*a):
                tok, _ = fn(*a)
                return tok, a[1]          # the pages as they came in
            return wrapped
        if kind == "half_batch":
            return outputs(fn, lambda t: rows(
                t, lambda x: x.at[x.shape[0] // 2:].set(0)))
        return outputs(fn, lambda t: rows(        # token_altered
            t, lambda x: x.at[0].set((x[0] + 1) % 256)))
    return plant


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_a_broken_step_is_not_correct(root, capsys, kind):
    res, _ = _run(root, capsys, fault=_break(kind), seed=7, seconds=2,
                  workload="smoke.busy")
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_cell_added_as_files_is_found(tmp_path, capsys):
    """A new configuration, traffic mix and cell are files plus entries
    in BENCHMARK.json; nothing else changes."""
    cells = {"smoke.chat": benchkit.SMOKE_CELL,
             "smoke.offline": benchkit.cell(load={"mode": "offline",
                                                  "queue_depth": 4})}
    root = benchkit.make_root(tmp_path, cells=cells)
    cell = spec.load_cell("smoke.offline", root=root)
    assert cell.cell["load"]["mode"] == "offline"
    assert cell.config["name"] == "smoke-dense"
    rc = harness.main(["--workload", "smoke.offline", "--seed", "5",
                       "--seconds", "3", "--trace", "0"],
                      require_accelerator=False, root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True and res["attempted"] >= 8
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_replays_are_told_apart_and_judged(tmp_path, monkeypatch):
    """A pool too small for the batch preempts; the preempted requests
    are replayed, the record marks exactly the replay dispatches, and
    the replayed streams come out correct."""
    benchkit.smoke_sizes(monkeypatch)
    tight = benchkit.cell(serve=dict(benchkit.SMOKE_CELL["serve"],
                                     n_pages=14),
                          load={"mode": "open", "rate_rps": 30.0})
    root = benchkit.make_root(tmp_path, cells={"smoke.tight": tight})
    runner = harness.Runner(spec.load_cell("smoke.tight", root=root),
                            traced=True)
    runner.build(3)
    out = runner.measure(3, 2.0)
    replays = runner._replays()
    runner.free()
    ds = runner.recorder.dispatches()
    assert replays > 0
    assert sum(d.replay for d in ds) == replays
    assert all(d.kind == "decode" and d.rows == 1 for d in ds if d.replay)
    win = [r for r in out["recs"] if r.in_window]
    picked = oracle.sample(out["recs"], 3, tokens=200, max_requests=16)
    assert any(r.req.n_preemptions for r in picked)
    ok, checks, _ = runner.judge(out["recs"], 3)
    assert ok, checks
    assert any(r.req.n_preemptions for r in win)


def test_benchmark_file_keeps_the_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) == len(b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "bench/cells" / f"{w['name']}.json").is_file()
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= 1
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for w in cells:                       # every cell reports enough
        rep = [m for m in b["end_to_end"] if w in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in rep} and len(rep) >= 2
        assert any(w in m.get("workloads", cells) for m in b["per_layer"])


def test_weights_follow_the_seed():
    from bench.lib.weights import make_weights
    arch = spec.load_arch(REPO / benchkit.SMOKE_CONFIG["reference"])
    tree = arch.shapes(arch.dims(benchkit.SMOKE_CONFIG))
    a = make_weights(tree, arch.std, 2**40 + 1, "bfloat16")
    b = make_weights(tree, arch.std, 2**40 + 1, "bfloat16")
    c = make_weights(tree, arch.std, 2**40 + 2, "bfloat16")
    wq = np.asarray(a["layers"]["mix"]["wq"], np.float32)
    assert (wq == np.asarray(b["layers"]["mix"]["wq"], np.float32)).all()
    assert not (wq == np.asarray(c["layers"]["mix"]["wq"], np.float32)).all()
    assert str(a["embed"]["tok"].dtype) == "bfloat16"
    assert str(a["final_norm"]["scale"].dtype) == "float32"
