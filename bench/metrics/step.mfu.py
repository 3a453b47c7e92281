"""Model step: forward operations of the valid tokens processed (prefill
tokens and decoded tokens; no padding, no replay) over the traced
part of the window, over chips x peak bf16 FLOP/s, in percent."""


def read(run):
    ds = [d for d in run.traced_dispatches() if not d.replay]
    t0, t1 = run.traced_host
    if not ds or t1 <= t0:
        return None
    flops = sum(d.work.flops for d in ds)
    return 100.0 * flops / ((t1 - t0) * run.chips * run.peak["bf16_flops"])
