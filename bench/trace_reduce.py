"""Reduce a `jax.profiler` trace to what the per-layer metrics read.

The trace is the `.xplane.pb` that `jax.profiler.stop_trace` writes,
read with `jax.profiler.ProfileData`.  What is taken from it:

- the window: the host span `bench.window` that the benchmark opens
  around its measured loop, on the profiler's own clock;
- device operations: the events of each TPU plane's "XLA Ops" line.
  On a backend with no device plane (the CPU, in tests) the events
  that carry an `hlo_op` stat on the host's threads stand in;
- program executions: the events of each TPU plane's "XLA Modules"
  line (on the CPU, one per `hlo_module`/`run_id` pair of the ops);
- host events: every event of the host plane, the benchmark's own
  `bench.*` annotations and JAX's dispatch and transfer events among
  them.

Conv operations are classified by what the trace shows, because the
program names neither its Pallas calls nor its stages: an operation is
a conv when its name, or its `long_name`, `hlo_category` or `tf_op`
stat, holds `custom-call` (a Mosaic kernel: the program's only custom
calls are its tile kernels) or `convolution` (an XLA convolution
fusion).  Everything else -- masks, pooling, padding, relayouts,
copies -- is "other".
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
CONV_MARKS = ("custom-call", "custom_call", "convolution")
# idle gaps shorter than this are counted, not attributed one by one
ATTRIBUTE_MIN_NS = 50_000
LONG_NS = 5_000_000


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns, profiler clock
    end: int
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> int:
        return self.end - self.start


def classify(ev: Event) -> str:
    """"conv" or "other" (see the module docstring for the rule)."""
    text = " ".join(
        [ev.name] + [str(ev.stats.get(k, ""))
                     for k in ("long_name", "hlo_category", "tf_op")]
    ).lower()
    return "conv" if any(m in text for m in CONV_MARKS) else "other"


_HLO = re.compile(r"%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")


def op_label(ev: Event) -> str:
    """A short stable name for a device op: on a TPU the event's name is
    the HLO instruction's text, shortened here to "instr: opcode -> type"."""
    m = _HLO.match(ev.name)
    if m is None:
        return ev.name[:120]
    return f"{m.group(1)}: {m.group(3)} -> {m.group(2)}"


def _fn(module: str) -> str:
    """A program's function: its module name without the fingerprint."""
    return module.split("(", 1)[0]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(e.name, start, start + int(e.duration_ns),
                         dict(e.stats)))
    return out


@dataclasses.dataclass
class Reduced:
    """A trace, reduced: the window, device ops and program executions
    (per device), and host events, all on one clock in ns."""

    window: Tuple[int, int]
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]

    # ------------------------------------------------------------ reads

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy_intervals(self, dev: str, lo=None, hi=None,
                       cls: Optional[str] = None) -> List[Tuple[int, int]]:
        """Union of the device's op intervals (of one class, if given),
        clipped to [lo, hi] (default: the window)."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        ivs = sorted(
            (max(e.start, lo), min(e.end, hi)) for e in self.ops[dev]
            if e.end > lo and e.start < hi
            and (cls is None or classify(e) == cls)
        )
        merged: List[Tuple[int, int]] = []
        for a, b in ivs:
            if merged and a <= merged[-1][1]:
                if b > merged[-1][1]:
                    merged[-1] = (merged[-1][0], b)
            else:
                merged.append((a, b))
        return merged

    def busy_s(self, dev: Optional[str] = None, lo=None, hi=None,
               cls: Optional[str] = None) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        devs = [dev] if dev else self.devices()
        if not devs:
            return 0.0
        tot = sum(b - a for d in devs
                  for a, b in self.busy_intervals(d, lo, hi, cls))
        return tot / len(devs) / 1e9

    def gaps(self, dev: str) -> List[Tuple[int, int]]:
        """Idle intervals of one device inside the window."""
        out = []
        t = self.window[0]
        for a, b in self.busy_intervals(dev):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def waves(self, dev: str) -> List[Event]:
        """Executions of the wave program on one device that started in
        the window, in order.  The served net runs as one jitted program
        per (bucket, batch), each named `jit_<fn>(<fingerprint>)`; the
        wave programs are those of the function whose executions take
        the most device time in the window (nothing else the benchmark
        calls runs that long)."""
        lo, hi = self.window
        mods = [m for m in self.modules.get(dev, ()) if lo <= m.start < hi]
        if not mods:
            return []
        tot: Dict[str, int] = {}
        for m in mods:
            tot[_fn(m.name)] = tot.get(_fn(m.name), 0) + m.dur
        top = max(tot, key=tot.get)
        return sorted((m for m in mods if _fn(m.name) == top),
                      key=lambda m: m.start)

    def op_time(self, dev: str, lo: int, hi: int,
                cls: Optional[str] = None) -> float:
        """Seconds of op time (union) of one class inside [lo, hi]."""
        return sum(b - a for a, b in self.busy_intervals(dev, lo, hi, cls)) / 1e9

    # -------------------------------------------------------- breakdown

    def top_ops(self, n: int = 10) -> List[list]:
        """The device ops that took the most time in the window, summed
        by name over the devices (and divided by their number)."""
        lo, hi = self.window
        tot: Dict[str, int] = {}
        for dev, evs in self.ops.items():
            for e in evs:
                if e.end > lo and e.start < hi:
                    key = f"{op_label(e)} [{classify(e)}]"
                    tot[key] = tot.get(key, 0) + min(e.end, hi) - max(e.start, lo)
        nd = max(len(self.ops), 1)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / nd / 1e9] for k, v in rows]

    def attribute(self, a: int, b: int) -> str:
        """What the host was doing in the idle gap [a, b]: the shortest
        host event that covers at least half of it, else the one that
        overlaps it most, else "unattributed"."""
        if not hasattr(self, "_short"):
            # long events are few and scanned whole; short ones are
            # found by their start
            evs = sorted((e for e in self.host if e.dur > 0),
                         key=lambda e: e.start)
            self._long = [e for e in evs if e.dur > LONG_NS]
            self._short = [e for e in evs if e.dur <= LONG_NS]
            self._starts = [e.start for e in self._short]
        i0 = bisect.bisect_left(self._starts, a - LONG_NS)
        i1 = bisect.bisect_right(self._starts, b)
        cands = self._short[i0:i1] + self._long
        best_cover = None
        best_overlap = (0, None)
        for e in cands:
            ov = min(e.end, b) - max(e.start, a)
            if ov <= 0:
                continue
            if 2 * ov >= (b - a):
                if best_cover is None or e.dur < best_cover.dur:
                    best_cover = e
            if ov > best_overlap[0]:
                best_overlap = (ov, e)
        pick = best_cover or best_overlap[1]
        return pick.name if pick is not None else "unattributed"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time in the window grouped by what the host was doing,
        the largest groups first, averaged over the devices.  Gaps under
        ATTRIBUTE_MIN_NS are summed as one group."""
        tot: Dict[str, int] = {}
        cnt: Dict[str, int] = {}
        for dev in self.devices():
            for a, b in self.gaps(dev):
                key = (self.attribute(a, b) if b - a >= ATTRIBUTE_MIN_NS
                       else f"gaps under {ATTRIBUTE_MIN_NS // 1000} us")
                tot[key] = tot.get(key, 0) + (b - a)
                cnt[key] = cnt.get(key, 0) + 1
        nd = max(len(self.ops), 1)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{k} (x{cnt[k]})", v / nd / 1e9] for k, v in rows]


# ----------------------------------------------------------------- loading

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Reduced:
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(_events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line))
    if not ops:  # no device plane: the CPU backend runs ops on host threads
        cpu_ops = [e for e in host if "hlo_op" in e.stats]
        host = [e for e in host if "hlo_op" not in e.stats]
        if cpu_ops:
            ops["/host:CPU"] = cpu_ops
            runs: Dict[tuple, Event] = {}
            for e in cpu_ops:
                k = (e.stats.get("hlo_module"), e.stats.get("run_id"))
                m = runs.get(k)
                if m is None:
                    runs[k] = Event(str(k[0]), e.start, e.end)
                else:
                    m.start, m.end = min(m.start, e.start), max(m.end, e.end)
            modules["/host:CPU"] = list(runs.values())
    wins = [e for e in host if e.name == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w = max(wins, key=lambda e: e.dur)
    return Reduced(window=(w.start, w.end), ops=ops, modules=modules,
                   host=host)
