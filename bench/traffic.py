"""Traffic from a mix file and a seed.

A mix is a JSON file under `bench/traffic/`:

    loop              "closed" (clients that each wait for their answer)
                      or "open" (independent users on a schedule)
    clients           closed loop: requests outstanding at all times
    rate_hz           open loop: arrivals per second, bursts included
    burst             open loop: {"size": n, "share": s}, bursts of n
                      simultaneous arrivals carrying the share s of all
    sides             {side: weight}: square image sides and their shares
    buckets           the server's size buckets
    max_batch         the server's wave size
    replicas          executors in the server's pool
    slo_s             completion limit the server schedules against
                      (null: full waves only)
    trace_rate_scale  open loop: the share of `rate_hz` offered in a
                      traced run's profiled segment (default 1), where
                      the tracer slows the host
    distinct_images   images made per side; requests cycle through them

The open-loop generator follows `repro.convserve.runtime.loadgen`
(Poisson arrivals merged with bursts, sides drawn by weight), with one
change for steadiness: every seed draws the same multiset of
inter-arrival gaps (the quantiles of the exponential law) and of sides
(the weights' exact counts), and the seed only orders them.  Two seeds
then offer the same work in a different order.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request of an open loop: when it is due (seconds from the
    window's start), its side, and which of that side's images."""

    t: float
    side: int
    image: int


def load(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    mix["sides"] = {int(k): float(v) for k, v in mix["sides"].items()}
    return mix


def side_counts(sides: dict, n: int) -> dict:
    """Exact counts of `n` draws by weight (largest remainder)."""
    total = sum(sides.values())
    raw = {s: n * w / total for s, w in sides.items()}
    counts = {s: int(np.floor(r)) for s, r in raw.items()}
    rest = sorted(raw, key=lambda s: (counts[s] - raw[s], s))
    for s in rest[: n - sum(counts.values())]:
        counts[s] += 1
    return counts


def open_arrivals(mix: dict, seconds: float, seed: int,
                  stream: int = 0) -> list:
    """The open loop's schedule over a window of `seconds`, from the
    seed's stream `stream` (0: the window, 1: a traced segment)."""
    rng = np.random.default_rng([int(seed), 0, stream])
    n = int(round(mix["rate_hz"] * seconds))
    burst = mix.get("burst") or {"size": 1, "share": 0.0}
    n_bursts = int(round(n * burst["share"] / burst["size"]))
    n_poisson = n - n_bursts * burst["size"]
    q = (np.arange(n_poisson) + 0.5) / max(n_poisson, 1)
    gaps = rng.permutation(-np.log1p(-q))
    times = list((np.cumsum(gaps) - gaps) * seconds / max(gaps.sum(), 1e-12))
    for j in range(n_bursts):
        times += [(j + 0.5) * seconds / n_bursts] * burst["size"]
    times.sort()
    counts = side_counts(mix["sides"], len(times))
    sides = rng.permutation(
        np.concatenate([np.full(c, s) for s, c in sorted(counts.items())]))
    seen: dict = {}
    out = []
    for t, s in zip(times, sides):
        s = int(s)
        k = seen.get(s, 0)
        seen[s] = k + 1
        out.append(Arrival(float(t), s, k % mix["distinct_images"]))
    return out


def make_images(mix: dict, seed: int, c_in: int) -> dict:
    """{side: [HWC float32 images]}: `distinct_images` standard-normal
    images per side, from the seed."""
    rng = np.random.default_rng([int(seed), 1])
    return {
        s: [rng.standard_normal((s, s, c_in), dtype=np.float32)
            for _ in range(mix["distinct_images"])]
        for s in sorted(mix["sides"])
    }
