"""The profiler-trace reduction: busy time as the union of operations,
idle gaps named by the host span over them, device time per module;
on a hand-made trace and on one recorded on a TPU v5e."""
from types import SimpleNamespace as NS

import pytest

from benchkit import REPO
from bench.lib import trace

#: 0.4 s of ``qwen3-0.6b.chat-long`` traced on one TPU v5e
#: (``--trace 1`` with the cell's trace cut to 0.4 s), gzipped
CHIP_TRACE = REPO / "bench" / "testdata" / "v5e_chat_long.xplane.pb.gz"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile():
    """One chip busy 100-300 (two ops), 400-450 (a loop around an op)
    and 900-1000 ns, inside a traced window 0-1000; host spans over
    it."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_fused_step(12)", 100, 200),
                                       ev("jit_paged_step(7)", 400, 50),
                                       ev("jit_paged_step(7)", 900, 150)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 100), ev("gather.2", 200, 100),
            ev("%while.7 = (s32[], bf16[2,8]{1,0:T(8,128)}) while(%a), "
               "body=%body", 400, 50),
            ev("%fusion.9 = bf16[8,128]{1,0:T(8,128)} fusion(%x), "
               "kind=kLoop", 410, 30),
            ev("gather.2", 900, 150)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced", 0, 1000), ev("bench.step", 80, 400),
        ev("bench.events", 480, 20), ev("bench.wait", 500, 390),
        ev("other", 0, 5)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_busy_is_the_union_of_operations():
    r = trace.reduce_profile(profile())
    assert r.window_s == pytest.approx(1000e-9)
    chip = r.chips["/device:TPU:0"]
    assert chip.busy_s == pytest.approx((200 + 50 + 100) * 1e-9)
    assert r.busy_s == chip.busy_s
    assert chip.gaps == [(0, 100), (300, 400), (450, 900)]


def test_modules_and_ops_are_clipped_to_the_window():
    r = trace.reduce_profile(profile())
    assert r.module_seconds(("jit_paged_step",)) == (2, pytest.approx(
        (50 + 100) * 1e-9))
    assert r.module_seconds(("jit_fused_step", "jit_paged_step"))[0] == 3
    ops = dict((n, s) for n, s in r.top_ops())
    assert ops["gather.2"] == pytest.approx(200e-9)
    assert ops["fusion.1"] == pytest.approx(100e-9)
    # a loop counts only the time its body's ops leave uncovered
    assert ops["%while.7 while (s32[], bf16[2,8])"] == pytest.approx(20e-9)
    assert ops["%fusion.9 fusion bf16[8,128]"] == pytest.approx(30e-9)


def test_gaps_are_named_by_the_host_span_over_them():
    r = trace.reduce_profile(profile())
    gaps = r.top_gaps()
    assert [g[0] for g in gaps] == ["bench.wait", "bench.step", "bench.step"]
    assert gaps[0][1] == pytest.approx(450e-9)


def test_a_trace_with_nothing_to_read_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_profile(NS(planes=[]))


def test_a_chip_trace_reduces():
    r = trace.load(str(CHIP_TRACE))
    assert list(r.chips) == ["/device:TPU:0"]
    assert 0 < r.busy_s <= r.window_s
    n, s = r.module_seconds(("jit_paged_step", "jit_fused_step",
                             "jit_chunk_step"))
    assert n > 0 and 0 < s <= r.window_s
    ops = r.top_ops()
    assert len(ops) == 10 and all(len(name) < 200 for name, _ in ops)
    assert sum(sec for _, sec in ops) <= r.busy_s
    # the host's gaps between dispatches fall in the harness's spans
    assert all(name.startswith("bench.") for name, _ in r.top_gaps())
