"""The control of ``correct``: the plain reference put in the program's
place at the precision below the configuration's must come out not
correct, where the program comes out correct.  On the CPU at the smallest
size where that can show (``benchkit.CONTROL_CONFIG``);
``bench/calibrate.py control`` makes the same readings on the chip at
each cell's own size."""
import json
import pathlib

import pytest

import benchkit
from benchkit import REPO
from bench.lib import harness, spec


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "off")
    benchkit.smoke_sizes(monkeypatch)


def _controls():
    return sorted({json.loads(p.read_text())["check"]["control"]
                   for p in (REPO / "bench" / "cells").glob("*.json")})


@pytest.mark.parametrize("precision", _controls())
def test_the_control_is_not_correct(tmp_path: pathlib.Path, precision):
    # the limit sits between the readings in CONTROL_CONFIG's comment
    c = benchkit.cell(check=dict(benchkit.SMOKE_CELL["check"],
                                 max_logit_gap=0.015))
    root = benchkit.make_root(tmp_path, cells={"smoke.chat": c},
                              config=benchkit.CONTROL_CONFIG)
    cell = spec.load_cell("smoke.chat", root=root)
    limit = cell.cell["check"]["max_logit_gap"]
    runner = harness.Runner(cell)
    for seed in (101, 104):
        runner.build(seed)
        out = runner.measure(seed, 4)
        runner.free()
        ok, checks, cmp = runner.judge(out["recs"], seed, control=precision)
        assert ok, checks                      # the program passes
        assert cmp["control_correct"] is False, cmp["control_checks"]
        assert cmp["served"] <= limit < cmp["control"], cmp
