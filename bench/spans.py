"""The program's own spans in a traced segment, and the wave program's
glue found by name: what the per-layer metrics of the runtime's
dispatch loop and the wave program's glue read.

`ServeRuntime` and `ReplicaPool` write each wave's host phases into the
profiler's timeline as `convserve.*` annotations, every one carrying the
wave's id as its `wave` stat: `convserve.replica.run` holds `assemble`,
`put`, `compute`, `fetch` and `crop`.  A wave is read when its
`convserve.replica.run` starts inside the window.  A program that writes
no such spans leaves every reader here with nothing to read (None).
The lengths of the spans inside a run are not read: under the profiler
the put holds the host events it records of the input's relayout, so a
traced put measures the profiler more than the transfer.

On the device, the tile kernels are named `convserve_tile_<family>_t<T>`
(the HLO instruction's name, which starts the op's name in the trace)
and XLA's convolutions carry `convolution` in their instruction's name
or opcode; every other op of the wave program is glue: pads, masks,
pools, slices, copies and relayouts.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import _HLO, Event, Reduced

PREFIX = "convserve."
RUN = "convserve.replica.run"
COMPUTE = "convserve.replica.compute"
TILE_KERNEL = "convserve_tile_"


def runs(tr: Optional[Reduced]) -> List[Event]:
    """The `convserve.replica.run` spans that start inside the window,
    in order of their start."""
    if tr is None:
        return []
    lo, hi = tr.window
    return sorted((e for e in tr.host
                   if e.name == RUN and lo <= e.start < hi),
                  key=lambda e: e.start)


def children(tr: Optional[Reduced], name: str) -> list:
    """(run, its `name` child) for each run read that has one: the span
    of the run's wave that lies inside the run."""
    rs = runs(tr)
    if not rs:
        return []
    by: Dict[object, List[Event]] = {}
    for e in tr.host:
        if e.name == name:
            by.setdefault(e.stats.get("wave"), []).append(e)
    out = []
    for r in rs:
        for e in by.get(r.stats.get("wave"), ()):
            if r.start <= e.start and e.end <= r.end:
                out.append((r, e))
                break
    return out


def mean_ms(ns: List[int]) -> Optional[float]:
    return sum(ns) / len(ns) / 1e6 if ns else None


def loop_gap_ms(tr: Optional[Reduced]) -> Optional[float]:
    """Mean gap from the end of one `convserve.replica.run` to the start
    of the next: completion, the loop's wake-up and the next dispatch."""
    rs = runs(tr)
    return mean_ms([b.start - a.end for a, b in zip(rs, rs[1:])])


def is_glue(ev: Event) -> bool:
    """Neither a named tile kernel nor an XLA convolution, by name."""
    m = _HLO.match(ev.name)
    instr, opcode = (m.group(1), m.group(3)) if m else (ev.name, "")
    if instr.startswith(TILE_KERNEL):
        return False
    return "convolution" not in instr and "convolution" not in opcode


def _union_ns(ivs: List[Tuple[int, int]]) -> int:
    tot, end = 0, None
    for a, b in sorted(ivs):
        if end is None or a > end:
            tot += b - a
            end = b
        elif b > end:
            tot += b - end
            end = b
    return tot


def wave_programs(tr: Optional[Reduced]) -> List[Event]:
    """For each wave read, the program execution on the first device that
    overlaps its `compute` span most.  (Overlap, not containment: the
    profiler's device and host clocks may disagree by a fraction of a
    millisecond.)"""
    if tr is None or not tr.devices():
        return []
    mods = tr.modules.get(tr.devices()[0], [])
    out = []
    for _, c in children(tr, COMPUTE):
        def overlap(m, c=c):
            return min(m.end, c.end) - max(m.start, c.start)

        best = max(mods, key=overlap, default=None)
        if best is not None and overlap(best) > 0:
            out.append(best)
    return out


def glue_ms(tr: Optional[Reduced]) -> Optional[float]:
    """Device time (the union of its op intervals) of the glue ops per
    execution of the wave program."""
    progs = wave_programs(tr)
    if not progs:
        return None
    ops = tr.ops[tr.devices()[0]]
    per = []
    for m in progs:
        per.append(_union_ns([(max(e.start, m.start), min(e.end, m.end))
                              for e in ops
                              if e.end > m.start and e.start < m.end
                              and is_glue(e)]))
    return mean_ms(per)


def covered_idle_share(tr: Reduced, min_ns: int = 50_000) -> Optional[float]:
    """Share of the device's idle time, in gaps of `min_ns` or more inside
    the window, that lies inside some `convserve.*` span on the host."""
    spans = [(e.start, e.end) for e in tr.host if e.name.startswith(PREFIX)]
    idle = covered = 0
    for dev in tr.devices():
        for a, b in tr.gaps(dev):
            if b - a < min_ns:
                continue
            idle += b - a
            covered += _union_ns([(max(s, a), min(t, b)) for s, t in spans
                                  if t > a and s < b])
    return covered / idle if idle else None
