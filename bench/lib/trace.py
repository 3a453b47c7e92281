"""Reduce a JAX profiler trace to what the per-layer metrics read.

Input is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device planes are named ``/device:TPU:<n>``; on each, the line ``XLA
Ops`` holds one event per operation that ran and ``XLA Modules`` one
per program execution (``jit_<name>(<id>)``).  Host spans that the
harness opens with ``jax.profiler.TraceAnnotation`` (``bench.*``) sit
on the host plane on the same clock.

Outputs, over the traced window (the ``bench.traced`` host span):

* per chip: busy seconds (the union of its operations' intervals), the
  idle gaps between them, device seconds and counts per module name,
  and self seconds per operation (its HLO name, opcode and type);
* the longest idle gaps, each named by the host span that covers most
  of it.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:"
WINDOW_SPAN = "bench.traced"
_MODULE_ID = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"^(.*?)\s([\w\-]+)\(")


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[9216,16]{...} fusion(...), ...`` ->
    ``%fusion.3 fusion bf16[9216,16]``: the instruction, its opcode and
    its result type without layouts (an op event is named by its HLO)."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    m = _OPCODE.match(_LAYOUT.sub("", rest))
    if not m:
        return head
    return f"{head} {m.group(2)} {m.group(1)[:80]}"


def self_times(events) -> Dict[str, float]:
    """Seconds per op name, each op's time less that of the ops nested
    in it (a loop's event spans its body's)."""
    out: Dict[str, float] = collections.Counter()
    stack: List[Tuple[float, str]] = []
    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        name, a, b = e
        while stack and stack[-1][0] <= a:
            stack.pop()
        out[name] += (b - a) * 1e-9
        if stack:
            out[stack[-1][1]] -= (b - a) * 1e-9
        stack.append((b, name))
    return dict(out)


@dataclasses.dataclass
class Chip:
    busy_s: float
    gaps: List[Tuple[float, float]]                  # (start_ns, end_ns)
    modules: Dict[str, List[float]]                  # name -> [count, s]
    ops: Dict[str, float]                            # name -> s


@dataclasses.dataclass
class Reduced:
    window_s: float
    chips: Dict[str, Chip]
    spans: List[Tuple[str, float, float]]            # host bench.* spans

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran anything."""
        if not self.chips:
            return 0.0
        return sum(c.busy_s for c in self.chips.values()) / len(self.chips)

    def module_seconds(self, prefixes) -> Tuple[int, float]:
        n, s = 0, 0.0
        for c in self.chips.values():
            for name, (cnt, sec) in c.modules.items():
                if name.startswith(tuple(prefixes)):
                    n += cnt
                    s += sec
        return n, s

    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = collections.Counter()
        for c in self.chips.values():
            for name, s in c.ops.items():
                tot[name] += s
        top = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, s] for n, s in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps over all chips, each named by the
        host span overlapping it most (``host`` where none does)."""
        gaps = sorted((g for c in self.chips.values() for g in c.gaps),
                      key=lambda g: g[0] - g[1])[:k]
        out = []
        for a, b in gaps:
            best, name = 0.0, "host"
            for sname, s0, s1 in self.spans:
                ov = min(b, s1) - max(a, s0)
                if ov > best:
                    best, name = ov, sname
            out.append([name, (b - a) * 1e-9])
        return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_profile(pd, *, window: Optional[Tuple[float, float]] = None
                   ) -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``.  ``window`` (ns) defaults
    to the ``bench.traced`` host span, else to the device events' span."""
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    if window is None:
        traced = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
        if traced:
            window = traced[0]
    chips: Dict[str, Chip] = {}
    for plane in devices:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = list(line.events)
            elif line.name == "XLA Modules":
                mods = list(line.events)
        if not ops:
            continue
        if window is None:
            window = (min(e.start_ns for e in ops),
                      max(e.start_ns + e.duration_ns for e in ops))
        w0, w1 = window

        def clip(e):
            return max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)

        busy = _union([clip(e) for e in ops if clip(e)[1] > clip(e)[0]])
        if not busy:
            continue
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        clipped = [(op_name(e.name),) + clip(e) for e in ops]
        op_s = self_times([c for c in clipped if c[2] > c[1]])
        mod: Dict[str, List[float]] = {}
        for e in mods:
            a, b = clip(e)
            if b > a:
                m = mod.setdefault(_MODULE_ID.sub("", e.name), [0, 0.0])
                m[0] += 1
                m[1] += (b - a) * 1e-9
        chips[plane.name] = Chip(sum(b - a for a, b in busy) * 1e-9, gaps,
                                 mod, op_s)
    if window is None:
        raise ValueError("the trace holds no device operation and no "
                         f"{WINDOW_SPAN} span")
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    return Reduced((window[1] - window[0]) * 1e-9, chips, spans)


def load(path: str, **kw) -> Reduced:
    """Reduce the ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``) at
    ``path``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return reduce_profile(ProfileData.from_serialized_xspace(f.read()),
                                  **kw)
    return reduce_profile(ProfileData.from_file(path), **kw)
