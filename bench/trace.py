"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time as the union of the intervals in
which an operation ran, device time per kernel, the device operations that
took most time, and the device's idle gaps attributed to the harness span
that was open on the host when each gap began.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane (a TPU); on a CPU backend, which has no device plane,
they are the host events that carry an ``hlo_op`` stat. Harness spans are
host events whose name starts with ``bench.`` (``TraceAnnotation``s opened
by the benchmark around its calls into the program). Both share the
profiler's clock.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: ops whose interval holds other ops of the same line (a loop's body runs
#: inside it); they count once in the busy union and never as an op of
#: their own in the breakdown
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: ``name`` is the HLO instruction's own name
    (``flash_attention_fwd.19``), ``detail`` the rest of what the trace
    says of it."""
    start: float            # ns
    end: float              # ns
    name: str
    detail: str = ""
    device: str = ""

    @property
    def base(self) -> str:
        """The name without its instance number: ``flash_attention_fwd``."""
        return re.sub(r"(\.\d+)+$", "", self.name)


def split_name(text: str) -> Tuple[str, str]:
    """A TPU trace names an op by its HLO text, ``%name.N = shape op(...)``:
    -> (``name.N``, the rest)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    return head.strip().lstrip("%"), rest


@dataclasses.dataclass(frozen=True)
class Span:
    start: float            # ns
    end: float              # ns
    name: str


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Tuple[List[Op], List[Span]]:
    """Device operations and harness spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if device:
                    if line.name == "XLA Ops":
                        name, rest = split_name(ev.name)
                        ops.append(Op(start, end, name, rest, plane.name))
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(start, end, ev.name))
                    continue
                if ev.duration_ns > 0 and "hlo_op" in _stats(ev):
                    ops.append(Op(start, end, ev.name, "", "/host:CPU"))
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return ops, spans


def window_of(spans: Sequence[Span]) -> Tuple[float, float]:
    """Bounds of the harness's measured window span."""
    for s in spans:
        if s.name == WINDOW_SPAN:
            return s.start, s.end
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def _clipped(ops: Iterable[Op], lo: float, hi: float):
    for o in ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            yield a, b


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: Sequence[Op], lo: float, hi: float,
            device: Optional[str] = None) -> float:
    """Time in [lo, hi] in which at least one operation ran (a union, so
    overlapping operations count once)."""
    sel = ops if device is None else [o for o in ops if o.device == device]
    return sum(b - a for a, b in union(_clipped(sel, lo, hi)))


def devices(ops: Sequence[Op]) -> List[str]:
    return sorted({o.device for o in ops})


def mean_busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    """Busy time averaged over the devices that ran operations."""
    devs = devices(ops)
    if not devs:
        return 0.0
    return sum(busy_ns(ops, lo, hi, d) for d in devs) / len(devs)


def kernel_ns(ops: Sequence[Op], kernels: Sequence[str], lo: float,
              hi: float) -> Tuple[float, int]:
    """Summed device time in [lo, hi] of the operations named after any of
    ``kernels`` (instance numbers aside), and their count. An op that only
    reads a kernel's result names it among its operands, not as its own
    name, and is not counted."""
    sel = [o for o in ops if o.base in kernels]
    return sum(b - a for a, b in _clipped(sel, lo, hi)), len(sel)


def top_ops(ops: Sequence[Op], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operation names (instance numbers aside) with the most
    device time, in seconds; loops that hold other ops are left out."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for o in ops:
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a and o.base not in CONTAINERS:
            tot[o.base] += b - a
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]


def _open_span(spans: Sequence[Span], t: float) -> str:
    """Innermost harness span (other than the window) open at ``t``."""
    best: Optional[Span] = None
    for s in spans:
        if s.start > t:
            break
        if s.end > t and s.name != WINDOW_SPAN and (
                best is None or s.start >= best.start):
            best = s
    return best.name if best is not None else WINDOW_SPAN


def idle_gaps(ops: Sequence[Op], spans: Sequence[Span], lo: float,
              hi: float, device: Optional[str] = None
              ) -> List[Tuple[str, float]]:
    """Every gap in [lo, hi] in which no operation ran on ``device`` (the
    first device when None), longest first, each named by the harness
    span open on the host as the gap began; seconds."""
    devs = devices(ops)
    if device is None and devs:
        device = devs[0]
    busy = union(_clipped([o for o in ops if o.device == device], lo, hi))
    gaps = []
    t = lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out = [(_open_span(spans, a), (b - a) / 1e9) for a, b in gaps]
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_span(gaps: Sequence[Tuple[str, float]]) -> Dict[str, float]:
    tot: Dict[str, float] = collections.defaultdict(float)
    for name, s in gaps:
        tot[name] += s
    return dict(tot)


@dataclasses.dataclass
class Reduced:
    """What the per-layer metric readers get from one traced window."""
    window_s: float
    busy_s: float
    ops: List[Op]
    spans: List[Span]
    lo: float
    hi: float

    def kernel_s(self, kernels: Sequence[str]) -> Tuple[float, int]:
        ns, n = kernel_ns(self.ops, kernels, self.lo, self.hi)
        return ns / 1e9, n

    def breakdown(self) -> Dict[str, list]:
        gaps = idle_gaps(self.ops, self.spans, self.lo, self.hi)
        return {"device_ops": [list(x) for x in
                               top_ops(self.ops, self.lo, self.hi)],
                "idle_gaps": [list(x) for x in gaps[:10]]}


def reduce_dir(trace_dir: str) -> Reduced:
    ops, spans = load(find_xplane(trace_dir))
    lo, hi = window_of(spans)
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=mean_busy_ns(ops, lo, hi) / 1e9,
                   ops=ops, spans=spans, lo=lo, hi=hi)
