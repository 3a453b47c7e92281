"""What the serve engine's phase spans and the model's named scopes put
in a profiler trace, read beside ``trace.py``'s busy and idle time.

Host spans: the harness's ``bench.*`` and the engine's ``serve.*``
(``serve.step`` and, nested in it, ``serve.admit``, ``serve.headroom``,
``serve.inputs``, ``serve.dispatch``, ``serve.wait``, ``serve.bank``,
``serve.replay``; see ``ServeEngine.step``), on the device planes'
clock.  Each idle instant of a chip is charged to the *innermost* span
open over it (``host`` where none is).

Device scopes: an op's scope path is the ``tf_op`` stat of its event
metadata in the XSpace proto, e.g. ``jit(fused_step)/while/body/
closed_call/attn/attn.core/...``.  ``jax.profiler.ProfileData`` does not
show event-metadata stats, so :func:`op_scopes` reads them from the
proto's wire format with the standard library alone.  A fused op
carries the scope of its root instruction, so ops fused across a scope
boundary count wholly under the root's scope.  An op the compiler added
has no path: it counts at the root of the step program it ran in, and
a layout copy among them (an HLO ``copy``) under ``COPY``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
from typing import Dict, Iterator, List, Optional, Tuple

from . import trace
from .readers import STEP_MODULES

#: host spans read here
SPAN_PREFIXES = ("bench.", "serve.")
#: the model's top-level scopes (models/lm.py, serve/step.py)
SCOPES = ("embed", "norm", "attn", "mlp", "kv_scatter", "unembed",
          "sample")
#: a step program's scope root: ``jit_fused_step`` -> ``jit(fused_step)``
STEP_ROOTS = tuple(f"jit({m[len('jit_'):]})" for m in STEP_MODULES)
LOOP = "while"
#: the compiler's layout copies outside the layer loop (no scope path)
COPY = "copy"
HOST = "host"

Span = Tuple[str, float, float]                  # name, start_ns, end_ns


# ------------------------------------------------- XSpace wire format
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _map_value(entry):
    """The value (field 2) of a protobuf map entry."""
    return next((v for num, v in _fields(entry) if num == 2), b"")


def op_scopes(raw: bytes) -> Dict[str, Dict[str, Optional[str]]]:
    """``{device plane: {op name: scope path}}`` from an XSpace proto:
    each device op's ``tf_op`` stat, its trailing ``:<type>`` cut.  An
    op name that two metadata entries give different paths maps to
    None.  (XSpace: planes are field 1; an XPlane's name is field 2,
    its event metadata field 4 and its stat metadata field 5, both maps
    keyed by id; an XEventMetadata's name is field 2 and its stats
    field 5; an XStat names its metadata in field 1 and holds a string
    in field 5, or a reference to a stat metadata's name in field 7.)"""
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(_map_value(v))
            elif f == 5:
                sm = dict(_fields(_map_value(v)))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        paths: Dict[str, Optional[str]] = {}
        for meta in metas:
            op, path = None, None
            for f, v in _fields(meta):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if st.get(1) in tf_op:
                        path = (bytes(st[5]).decode() if 5 in st
                                else stat_names.get(st.get(7), ""))
            if op is None or path is None:
                continue
            path = path[:-1] if path.endswith(":") else path
            paths[op] = path if paths.get(op, path) == path else None
        out[name] = paths
    return out


# ------------------------------------------------------- the reduction
def innermost(spans: List[Span]) -> List[Span]:
    """The time line cut at every span edge, each piece that some span
    covers named by the innermost one open over it: the latest started,
    then the shortest.  Returns (name, start, end) in time order."""
    edges = sorted({x for _, a, b in spans for x in (a, b)})
    order = sorted(spans, key=lambda s: s[1])
    out: List[Span] = []
    open_: List[Span] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            open_.append(order[i])
            i += 1
        open_ = [s for s in open_ if s[2] > a]
        if open_:
            out.append((max(open_, key=lambda s: (s[1], -s[2]))[0], a, b))
    return out


def _cover(a: float, b: float, pieces: List[Span], starts: List[float]
           ) -> Dict[str, float]:
    """ns of [a, b) under each name of ``pieces`` (``innermost``'s)."""
    out: Dict[str, float] = collections.Counter()
    j = max(bisect.bisect_right(starts, a) - 1, 0)
    while j < len(pieces) and pieces[j][1] < b:
        name, pa, pb = pieces[j]
        ov = min(b, pb) - max(a, pa)
        if ov > 0:
            out[name] += ov
        j += 1
    return out


def _scope_of(path: str) -> Optional[str]:
    """The first of ``SCOPES`` on ``path``; ``LOOP`` for an op of the
    layer loop under none (the scan's slicing and stacking); ``COPY``
    for a layout copy at a program's root; None for anything else."""
    parts = path.split("/")
    for p in parts:
        if p in SCOPES:
            return p
    if LOOP in parts:
        return LOOP
    return COPY if parts[1:] == [COPY] else None


def _is_copy(op: str) -> bool:
    """Whether the HLO instruction ``op`` (``%copy.89 = bf16[...] ...``)
    is a copy: XLA names an instruction after its opcode."""
    return op.lstrip("%").split(" ", 1)[0].split(".", 1)[0] in (
        "copy", "copy-start", "copy-done")


@dataclasses.dataclass
class Phases:
    reduced: trace.Reduced                # the busy/idle reduction
    window: Tuple[float, float]           # ns
    spans: List[Span]                     # bench.* and serve.* in it
    scoped: Dict[str, float]              # step-program path -> self s
    #                                       (``<root>/`` with no path)
    executions: int                       # step programs run in it

    def __post_init__(self):
        self.pieces = innermost(self.spans)
        self._starts = [p[1] for p in self.pieces]

    def _gaps(self):
        for chip in self.reduced.chips.values():
            for a, b in chip.gaps:
                cov = _cover(a, b, self.pieces, self._starts)
                yield a, b, cov

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds in the window by the innermost host span open
        over them (``host`` where none is), averaged over the chips."""
        out: Dict[str, float] = collections.Counter()
        for a, b, cov in self._gaps():
            for name, ns in cov.items():
                out[name] += ns
            out[HOST] += (b - a) - sum(cov.values())
        n = max(len(self.reduced.chips), 1)
        return {k: v * 1e-9 / n for k, v in
                sorted(out.items(), key=lambda kv: -kv[1]) if v > 0}

    def top_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps over all chips, each named by the
        innermost span that covers most of it (``host`` where none
        does)."""
        gaps = sorted(self._gaps(), key=lambda g: g[0] - g[1])[:k]
        return [[max(cov, key=cov.get) if cov else HOST, (b - a) * 1e-9]
                for a, b, cov in gaps]

    def span_list(self, name: str) -> List[Tuple[float, float]]:
        return [(a, b) for n, a, b in self.spans if n == name]

    def scope_seconds(self, scope: str) -> float:
        """Device seconds of step-program ops whose scope path holds
        ``scope`` (``attn``, ``attn.gather``, ...)."""
        return sum(s for path, s in self.scoped.items()
                   if scope in path.split("/"))

    def by_scope(self) -> Dict[str, float]:
        """Step-program device seconds by top-level scope (``SCOPES``),
        ``while`` for the layer loop's unscoped ops, ``copy`` for the
        compiler's layout copies outside it and ``other`` for the rest
        (ops outside the loop under no scope); empty when the programs
        carry no scope."""
        out: Dict[str, float] = collections.Counter()
        for path, s in self.scoped.items():
            out[_scope_of(path) or "other"] += s
        return dict(out) if any(k in SCOPES for k in out) else {}


def _host_spans(pd) -> List[Span]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes
            if not plane.name.startswith(trace.DEVICE_PREFIX)
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIXES)]


def _device_lines(pd):
    """(plane name, op events, step-program runs as (start, end, scope
    root) in time order) of each device plane that ran operations."""
    for plane in pd.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if lines.get("XLA Ops"):
            runs = []
            for e in lines.get("XLA Modules", []):
                name = trace._MODULE_ID.sub("", e.name)
                if name in STEP_MODULES:
                    runs.append((e.start_ns, e.start_ns + e.duration_ns,
                                 f"jit({name[len('jit_'):]})"))
            yield plane.name, lines["XLA Ops"], sorted(runs)


def reduce_profile(pd, scopes: Dict[str, Dict[str, Optional[str]]]
                   ) -> Phases:
    """``pd``: a ``jax.profiler.ProfileData``; ``scopes``: its
    ``op_scopes``.  The window is ``trace.reduce_profile``'s: the
    ``bench.traced`` span, else the first chip's operations' extent."""
    spans = _host_spans(pd)
    window = next(((a, b) for n, a, b in spans if n == trace.WINDOW_SPAN),
                  None)
    if window is None:
        for _, ops, _ in _device_lines(pd):
            window = (min(e.start_ns for e in ops),
                      max(e.start_ns + e.duration_ns for e in ops))
            break
    reduced = trace.reduce_profile(pd, window=window)
    w0, w1 = window
    scoped: Dict[str, float] = collections.Counter()
    for plane, ops, runs in _device_lines(pd):
        paths = scopes.get(plane, {})
        starts = [r[0] for r in runs]

        def root_at(t):
            i = bisect.bisect_right(starts, t) - 1
            return runs[i][2] if i >= 0 and t < runs[i][1] else None
        # an op the compiler added (a layout copy) has no path: it is
        # put at the root of the step program it ran in
        clipped = [((e.name, root_at(e.start_ns)), max(e.start_ns, w0),
                    min(e.start_ns + e.duration_ns, w1)) for e in ops]
        for (name, root), s in trace.self_times(
                [c for c in clipped if c[2] > c[1]]).items():
            path = paths.get(name) or (
                root and root + "/" + (COPY if _is_copy(name) else ""))
            if path and path.split("/")[0] in STEP_ROOTS:
                scoped[path] += s
    spans = sorted((s for s in spans if s[0] != trace.WINDOW_SPAN
                    and s[2] > w0 and s[1] < w1), key=lambda s: s[1])
    return Phases(reduced, window, spans, dict(scoped),
                  reduced.module_seconds(STEP_MODULES)[0])


def load(path: str) -> Phases:
    """Read the ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``) at
    ``path``."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw),
                          op_scopes(raw))


# ------------------------------------------------------- the numbers
def _in_steps(ph: Phases, name: str) -> Optional[List[float]]:
    """ns of ``name`` spans inside each ``serve.step`` span that lies in
    the window; None where there is none."""
    w0, w1 = ph.window
    steps = [(a, b) for a, b in ph.span_list("serve.step")
             if w0 <= a and b <= w1]
    if not steps:
        return None
    inner = ph.span_list(name)
    return [sum(ib - ia for ia, ib in inner if a <= ia and ib <= b)
            for a, b in steps]


def sched_host_ms(ph: Phases) -> Optional[float]:
    """Scheduler: mean over the ``serve.step`` spans inside the window
    of their host time outside the step program: duration less their
    ``serve.dispatch`` and ``serve.wait``."""
    w0, w1 = ph.window
    steps = [b - a for a, b in ph.span_list("serve.step")
             if w0 <= a and b <= w1]
    if not steps:
        return None
    busy = [d + w for d, w in zip(_in_steps(ph, "serve.dispatch"),
                                  _in_steps(ph, "serve.wait"))]
    return (sum(steps) - sum(busy)) / len(steps) * 1e-6


def runtime_dispatch_ms(ph: Phases) -> Optional[float]:
    """Runtime: mean over the window's ``serve.step`` spans of their
    ``serve.dispatch`` time, the host inside the step program's call
    (argument handling, launch, and waiting for the device memory its
    outputs need)."""
    d = _in_steps(ph, "serve.dispatch")
    return None if d is None else sum(d) / len(d) * 1e-6


def kv_headroom_ms(ph: Phases) -> Optional[float]:
    """KV cache: ``serve.headroom`` seconds in the window (page growth,
    copy-on-write, eviction, preemption) per ``serve.step`` starting in
    it."""
    w0, w1 = ph.window
    n = sum(w0 <= a < w1 for a, _ in ph.span_list("serve.step"))
    if not n:
        return None
    s = sum(max(min(b, w1) - max(a, w0), 0)
            for a, b in ph.span_list("serve.headroom"))
    return s / n * 1e-6


def step_attn_ms(ph: Phases) -> Optional[float]:
    """Paged attention: device ms under the ``attn`` scope per
    step-program execution."""
    s = ph.scope_seconds("attn")
    return s / ph.executions * 1e3 if s and ph.executions else None


def step_scan_io_ms(ph: Phases) -> Optional[float]:
    """Step programs: device ms of the layer loop's ops under no model
    scope (the per-layer slice and write-back of the stacked page pools
    and weights) per step-program execution; None where the programs
    carry no scope."""
    s = ph.by_scope().get(LOOP)
    return s / ph.executions * 1e3 if s and ph.executions else None


def step_layout_copy_ms(ph: Phases) -> Optional[float]:
    """Step programs: device ms of the compiler's layout copies outside
    the layer loop (whole page pools re-laid for the loop and back) per
    step-program execution; 0 where there are none, None where the
    programs carry no scope."""
    by = ph.by_scope()
    if not by or not ph.executions:
        return None
    return by.get(COPY, 0.0) / ph.executions * 1e3


METRICS = {"sched.host_ms": sched_host_ms,
           "runtime.dispatch_ms": runtime_dispatch_ms,
           "kv.headroom_ms": kv_headroom_ms,
           "step.attn_ms": step_attn_ms,
           "step.scan_io_ms": step_scan_io_ms,
           "step.layout_copy_ms": step_layout_copy_ms}
