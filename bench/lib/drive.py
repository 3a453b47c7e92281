"""The measured window: offer load, step the backend, time every token.

The client keeps its own clock, in seconds from the moment the window
opens.  A request's time starts at its *due* time; a token's time is
the moment ``step()`` returned it through ``drain_events``.  Before the
window opens the cell's own load runs for ``warm`` seconds, untimed, so
that the window finds the engine at the occupancy that load holds.
Requests due in the window are followed to their last token: after the
window closes the loop goes on offering load and stepping until they
finish, or until the cell's cap, past which the unfinished ones count
as failed.

The backend is driven only through the ``ServeBackend`` protocol
(``submit``, ``step``, ``drain_events``, ``n_inflight``, ``capacity``,
``stats``), and its program bundle only through its public step
programs (``decode``, ``chunk``, ``fused``, ``verify``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .traffic import ReqSpec
from .work import Work, decode_rows, prefill_rows

#: the step programs of a bundle, by their public names
STEP_PROGRAMS = ("decode", "chunk", "fused", "verify")


@dataclasses.dataclass
class Rec:
    spec: ReqSpec
    req: object                      # the program's Request
    due: float
    submit: float
    in_window: bool
    first: Optional[float] = None
    last: Optional[float] = None
    done: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Dispatch:
    kind: str                        # decode | chunk | fused | verify
    t0: float
    t1: float
    work: Work
    rows: int                        # active decode rows (0: prefill only)
    replay: bool = False


class Wrapped:
    """A program bundle whose step programs are passed through each of
    ``wraps`` (``wrap(kind, fn) -> fn``, innermost first); everything
    else is the bundle's own.  Engines built over it keep the wrapped
    callables."""

    def __init__(self, inner, wraps):
        self._inner = inner
        self._wraps = list(wraps)
        self._done: Dict[str, Callable] = {}

    def __getattr__(self, name):
        fn = getattr(self._inner, name)
        if name not in STEP_PROGRAMS:
            return fn
        if name not in self._done:
            for w in self._wraps:
                fn = w(name, fn)
            self._done[name] = fn
        return self._done[name]


class Recorder:
    """Notes, per dispatch of a step program, its host time and the
    inputs that say what work it asks for (traced runs only).  The
    inputs are kept as they came and read back after the window, so the
    record adds no device-to-host copy to the measured path.

    A preempted request is replayed one single-row decode dispatch per
    token it had; the backend's ``n_replay_steps`` counter, read after
    each step (``end_step``), says how many of a step's dispatches were
    replays."""

    def __init__(self, dims, clock: Callable[[], float]):
        self.dims = dims
        self.clock = clock
        self.reset(0)

    def reset(self, n_replay_steps: int) -> None:
        self._raw: List[tuple] = []
        self._replays: List[tuple] = []     # (first, end, replays)
        self._mark = 0
        self._seen = n_replay_steps

    def wrap(self, kind: str, fn):
        def call(*args):
            if kind == "chunk":
                keep = (args[4], args[5])
            elif kind == "fused":
                keep = (args[1]["lengths"], args[2].shape[1], args[5],
                        args[6])
            else:                                   # decode, verify
                keep = (args[1]["lengths"], args[2].shape[1])
            # a host array may be edited in place after the call
            keep = tuple(np.array(x) if isinstance(x, np.ndarray) else x
                         for x in keep)
            t0 = self.clock()
            out = fn(*args)
            self._raw.append((kind, t0, self.clock(), keep))
            return out
        return call

    def end_step(self, n_replay_steps: int) -> None:
        if n_replay_steps > self._seen:
            self._replays.append((self._mark, len(self._raw),
                                  n_replay_steps - self._seen))
        self._seen = n_replay_steps
        self._mark = len(self._raw)

    def dispatches(self) -> List[Dispatch]:
        """Every dispatch since ``reset``, with its work."""
        out = []
        for kind, t0, t1, keep in self._raw:
            if kind == "chunk":
                work, rows = prefill_rows(self.dims, np.asarray(keep[0]),
                                          np.asarray(keep[1])), 0
            else:
                lengths = np.asarray(keep[0])
                live = lengths[lengths > 0]
                work, rows = decode_rows(self.dims, live, T=keep[1]), len(live)
                if kind == "fused":
                    work = work + prefill_rows(self.dims, np.asarray(keep[2]),
                                               np.asarray(keep[3]))
            out.append(Dispatch(kind, t0, t1, work, rows))
        for first, end, n in self._replays:
            for d in out[first:end]:
                if n and d.kind == "decode" and d.rows == 1:
                    d.replay, n = True, n - 1
        return out


def span(traced: bool):
    if traced:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def run_window(backend, source, make_request, *, seconds: float,
               cap: float, mode: str, depth: int = 0,
               clock: Callable[[], float],
               on_tick: Optional[Callable[[float], None]] = None,
               after_step: Optional[Callable[[], None]] = None,
               traced: bool = False) -> Dict:
    """Drive ``backend`` from the ramp (``clock`` starts below 0) through
    the window ``[0, seconds)`` and the drain.  ``source`` yields
    ``ReqSpec`` in due order; ``mode`` is ``open`` (submit each when
    due) or ``offline`` (keep ``depth`` requests queued beyond the batch
    until the window closes)."""
    S = span(traced)
    recs: Dict[int, Rec] = {}
    spec = next(source)
    tokens_in_window = 0
    window_left = 0                          # in-window requests unfinished
    lag_max = 0.0
    inflight_at_open = None

    def submit(s: ReqSpec, due: float):
        nonlocal window_left, lag_max
        with S("bench.submit"):
            req = make_request(s, due)
            backend.submit(req)
        rec = Rec(s, req, due, clock(), 0.0 <= due < seconds)
        recs[s.rid] = rec
        window_left += rec.in_window
        if rec.in_window:
            lag_max = max(lag_max, rec.submit - due)

    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        if inflight_at_open is None and now >= 0.0:
            inflight_at_open = backend.n_inflight
        with S("bench.generate"):
            if mode == "open":
                while spec.due <= now:
                    submit(spec, spec.due)
                    spec = next(source)
            elif now < seconds:
                while backend.n_inflight < backend.capacity + depth:
                    submit(spec, now)
                    spec = next(source)
        if now >= seconds and window_left == 0:
            break
        if now >= seconds + cap:
            break
        if backend.n_inflight:
            with S("bench.step"):
                backend.step(now)
            t = clock()
            if after_step is not None:
                after_step()
            with S("bench.events"):
                for ev in backend.drain_events():
                    rec = recs[ev.rid]
                    if ev.tokens:
                        if rec.first is None:
                            rec.first = t
                        rec.last = t
                        rec.tokens.extend(ev.tokens)
                        if 0.0 <= t <= seconds:
                            tokens_in_window += len(ev.tokens)
                    if ev.finished:
                        rec.done = t
                        window_left -= rec.in_window
        elif mode == "open":
            with S("bench.wait"):
                time.sleep(max(0.0, min(spec.due - clock(), 0.05)))
    end = clock()
    return {"recs": list(recs.values()), "tokens_in_window": tokens_in_window,
            "end": end, "lag_max": lag_max,
            "inflight_at_open": inflight_at_open}
