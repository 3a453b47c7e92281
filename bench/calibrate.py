"""Readings that set a cell's limits and load, in one process on the chip.

    python3 bench/calibrate.py control --workload <name> --seeds 1,2,3 \
        --seconds 20
    python3 bench/calibrate.py sweep --workload <name> --rates 1,2,3 \
        --seconds 20 --seed 1

``control`` runs, for each seed, a window at the cell's own load and
size (its ramp included), then reads the number ``correct`` compares
(the widest gap of a served token's logit below the reference's best)
for the program and for the control (the reference at int8 or fp8, put
in the program's place), and judges both by the cell's own limits
(``correct`` and ``control_correct``).  The largest program reading is
the limit's lower end; the smallest control reading its upper end.

``sweep`` offers an open-loop cell's traffic at each rate, each window
after the cell's ramp at that rate, and prints the tokens per second
completed, the latency tails, and whether the backlog grew (requests
still waiting when the window closed).

Each prints one JSON line per seed or rate.  Exits 2 without an
accelerator.
"""
import argparse
import json
import os
import sys
import time
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from bench.lib import harness, spec  # noqa: E402
from bench.lib.readers import pct, tpots  # noqa: E402


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def control(runner, seeds, seconds, precisions=("int8", "fp8")):
    for seed in seeds:
        runner.build(seed)
        out = runner.measure(seed, seconds)
        runner.free()
        gaps, verdicts = {}, {}
        for q in precisions:
            ok, checks, cmp = runner.judge(out["recs"], seed, control=q)
            gaps[q] = cmp["control"]
            verdicts[q] = cmp["control_correct"]
        win = [r for r in out["recs"] if r.in_window]
        print(json.dumps({
            "seed": seed, "correct": ok, "program_gap": cmp["served"],
            "control_gap": gaps, "control_correct": verdicts,
            "tokens": cmp["tokens"],
            "attempted": len(win),
            "preempted": sum(r.req.n_preemptions > 0 for r in win),
            "failed": sum(r.done is None for r in win),
            "compiles": out["compiles"]}), flush=True)


def sweep(runner, rates, seconds, seed):
    runner.build(seed)
    for rate in rates:
        out = runner.measure(seed, seconds, rate=rate)
        win = [r for r in out["recs"] if r.in_window]
        late = [r for r in win if r.first is None or r.first > seconds]
        ttft = [((r.first if r.first is not None else out["end"]) - r.due)
                for r in win]
        half = [t for r, t in zip(win, ttft) if r.due >= seconds / 2]
        first = [t for r, t in zip(win, ttft) if r.due < seconds / 2]
        tp = tpots(types.SimpleNamespace(window_recs=win))
        print(json.dumps({
            "rate": rate, "attempted": len(win),
            "failed": sum(r.done is None for r in win),
            "output_tok_s": out["tokens_in_window"] / seconds,
            "ttft_p50_s": pct(ttft, 50), "ttft_p90_s": pct(ttft, 90),
            "ttft_p50_first_half_s": pct(first, 50),
            "ttft_p50_second_half_s": pct(half, 50),
            "no_token_by_close": len(late),
            "tpot_p50_s": pct(tp, 50), "tpot_p90_s": pct(tp, 90),
            "preempted": sum(r.req.n_preemptions > 0 for r in win),
            "inflight_at_open": out["inflight_at_open"],
            "drain_s": out["end"] - seconds,
            "compiles": out["compiles"]}), flush=True)
        # let the backend empty before the next rate
        while runner.backend.n_inflight:
            runner.backend.step(float("inf"))
            runner.backend.drain_events()
    runner.free()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("control", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if harness.check_devices(cell, True) is None:
        return 2
    t = time.perf_counter()
    runner = harness.Runner(cell)
    if args.mode == "control":
        control(runner, _ints(args.seeds), args.seconds)
    else:
        sweep(runner, [float(r) for r in args.rates.split(",")], args.seconds,
              args.seed)
    harness.log(f"calibrate: {time.perf_counter() - t:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
