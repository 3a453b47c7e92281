"""KV cache: share of the requests due in the window that were
preempted at least once (pages ran out), in percent."""


def read(run):
    w = run.window_recs
    return 100.0 * sum(r.req.n_preemptions > 0 for r in w) / len(w) \
        if w else None
