"""The program's own spans (``repro.obs``) read against a traced window.

The program records each phase of its work in an in-memory log, stamped
with ``time.time_ns()``; the profiler's trace holds the harness's spans
and the device's operations on the trace's relative clock. This module
joins the two and answers two questions for the per-layer metrics:

- how long each set-up phase took (``setup_seconds``);
- how much of the device's idle time in the window fell while the host
  was in a given phase (``idle_share``). Each idle interval of the first
  device is split by its overlap with the phase's spans, so a gap that is
  half under ``train.data`` counts half.

This run's records are those from the newest ``train.init`` on. With k
``bench.run`` spans in the trace, the window's records are the last k
``train.run`` spans, one inside each ``bench.run``; set-up is every record
that ended before the first of them. Each ``train.run`` opens a few
microseconds after its ``bench.run``, so the offset from the program's
clock to the trace's is the largest ``bench.run`` start minus ``train.run``
start over the chunks: it places the earliest-opening ``train.run`` at
its ``bench.run``'s start and none before. Every shifted ``train.run``
must end inside its ``bench.run``; the least room left at those ends
(``Joined.residual_ns``) bounds how early the shifted spans can sit.

A program without ``repro.obs``, or a run that recorded no ``train.init``,
gives no reading: the readers return None.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from bench import trace as bench_trace

RUN_SPAN = "bench.run"
PROGRAM_RUN = "train.run"
PROGRAM_INIT = "train.init"


def records() -> Optional[list]:
    """This run's program spans by start: those from the newest
    ``train.init`` on; None where the program records none."""
    try:
        from repro import obs
    except ImportError:
        return None
    recs = obs.spans()
    starts = [r.t0_ns for r in recs if r.name == PROGRAM_INIT]
    if not starts:
        return None
    first = max(starts)
    return sorted((r for r in recs if r.t0_ns >= first),
                  key=lambda r: r.t0_ns)


@dataclasses.dataclass
class Joined:
    setup: list             # records that ended before the window began
    records: list           # all of this run's records
    offset_ns: int          # program clock + offset = trace clock
    residual_ns: int        # least room between a shifted end and its chunk

    def shifted(self, names: Sequence[str]) -> List[Tuple[float, float]]:
        """The named records on the trace's clock, merged."""
        return bench_trace.union((r.t0_ns + self.offset_ns,
                                  r.t1_ns + self.offset_ns)
                                 for r in self.records if r.name in names)


def join(recs: list, spans: Sequence[bench_trace.Span]) -> Joined:
    """Join this run's records (``records()``) to the trace's harness
    spans; raises where the chunks and the program's runs do not pair."""
    chunks = sorted((s for s in spans if s.name == RUN_SPAN),
                    key=lambda s: s.start)
    runs = [r for r in recs if r.name == PROGRAM_RUN]
    if not chunks or len(runs) < len(chunks):
        raise ValueError(f"{len(chunks)} {RUN_SPAN} spans in the trace but "
                         f"{len(runs)} {PROGRAM_RUN} records")
    runs = runs[-len(chunks):]
    offset = max(int(c.start) - r.t0_ns for c, r in zip(chunks, runs))
    room = [int(c.end) - (r.t1_ns + offset) for c, r in zip(chunks, runs)]
    if min(room) < 0:
        raise ValueError(f"a shifted {PROGRAM_RUN} ends {-min(room)} ns "
                         f"after its {RUN_SPAN}: the clocks do not join")
    first = runs[0].t0_ns
    return Joined(setup=[r for r in recs if r.t1_ns <= first], records=recs,
                  offset_ns=offset, residual_ns=min(room))


def joined(ctx) -> Optional[Joined]:
    red = ctx.get("trace")
    recs = records()
    if red is None or recs is None:
        return None
    return join(recs, red.spans)


def setup_seconds(ctx, name: str, outcome: Optional[str] = None
                  ) -> Optional[float]:
    """Summed seconds of the ``name`` spans in set-up (those that ended
    with ``outcome``, where given)."""
    j = joined(ctx)
    if j is None:
        return None
    return sum((r.seconds for r in j.setup
                if r.name == name and outcome in (None, r.outcome)), 0.0)


def idle_intervals(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which no operation ran on the first
    device."""
    devs = bench_trace.devices(ops)
    busy = bench_trace.union(bench_trace._clipped(
        [o for o in ops if devs and o.device == devs[0]], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def overlap_ns(xs: Sequence[Tuple[float, float]],
               ys: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_share(ctx, names: Sequence[str]) -> Optional[float]:
    """Percent of the traced window in which the first device was idle
    while a ``names`` span was open on the host."""
    j = joined(ctx)
    if j is None:
        return None
    red = ctx["trace"]
    if red.hi <= red.lo:
        return None
    idle = idle_intervals(red.ops, red.lo, red.hi)
    return 100.0 * overlap_ns(idle, j.shifted(names)) / (red.hi - red.lo)
