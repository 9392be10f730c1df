"""The paper's "wisdom file" (S7): measured R tuning, cached on disk.

    from repro.core.tune import tuned_r, predict_r
    r = tuned_r(h=56, w=56, c_in=64, c_out=64)   # measures once, caches
    r = predict_r(c_in=64, c_out=64)             # analytic only, no timing

The analytical bounds (core.analysis) give the feasible range; within it we
time the fused convolution at a few candidate R values and store the
winner keyed by (transform family, tile size, layer geometry, backend) --
family in the key, so a Winograd-R and an FFT-T tune for the same layer
can never collide or overwrite each other.  `predict_r` is the
non-measuring path used by the convserve planner when tuning is disabled:
it picks the candidate that satisfies the R >= 2 CMR_fast lower bound while
staying within the (family-exact, `TileAlgebra`-priced) private-memory
upper bound.

Every entry point takes an optional `transform` (a `core.transforms`
Transform); the m/k keyword pair is the historical Winograd-only spelling
and resolves to `WinogradTransform(m, k)`.
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analysis, transforms
from repro.core.ioutil import atomic_write_text
from repro.core.pipeline import fused_tile_conv
from repro.kernels.fused_tile.blocks import BlockConfig

# the checkout root: planning reads no state from outside the checkout
_DEFAULT_WISDOM = pathlib.Path(__file__).resolve().parents[3] / "repro_wisdom.json"
_CANDIDATES = (4, 8, 16, 24, 32, 48)
_WISDOM_ENV = "REPRO_WISDOM"


def _wisdom_path(wisdom_path=None) -> pathlib.Path:
    """Explicit path > $REPRO_WISDOM (the CI artifact seam) > default."""
    if wisdom_path is not None:
        return pathlib.Path(wisdom_path)
    env = os.environ.get(_WISDOM_ENV)
    return pathlib.Path(env) if env else _DEFAULT_WISDOM


def _resolve_transform(
    transform: Optional[transforms.Transform], k: int, m: int
) -> transforms.Transform:
    return (
        transform
        if transform is not None
        else transforms.WinogradTransform(m=m, k=k)
    )


def _key(tr: transforms.Transform, h, w, c_in, c_out) -> str:
    """Wisdom key: backend + transform family + tile size + geometry."""
    return (
        f"{jax.default_backend()}:{tr.family}:{h}x{w}x{c_in}->{c_out}"
        f":k{tr.k}:t{tr.t}"
    )


def _load(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


# Wisdom values are either a bare int R (legacy files) or a stamped entry
# {"r": int, "gen": int, "ts": float}.  `gen` is a monotonic generation
# counter per wisdom file; `ts` is wall-clock seconds.  Stamps let online
# measurement layers (convserve.adapt) and offline tuning expire each
# other's entries by age or generation instead of silently shadowing.


def _entry_r(value) -> Optional[int]:
    """R from a wisdom value; None when the entry carries only other
    dimensions (e.g. a block shape tuned before any R pass)."""
    if isinstance(value, dict):
        return int(value["r"]) if "r" in value else None
    return int(value)


def _entry_gen(value) -> int:
    return int(value.get("gen", 0)) if isinstance(value, dict) else 0


def _entry_ts(value) -> float:
    return float(value.get("ts", 0.0)) if isinstance(value, dict) else 0.0


def wisdom_generation(wisdom_path: Optional[pathlib.Path] = None) -> int:
    """Highest generation stamped in the wisdom file (0 when empty or
    fully legacy).  Writers stamp `wisdom_generation() + 1`."""
    path = _wisdom_path(wisdom_path)
    wisdom = _load_cached(path)
    return max((_entry_gen(v) for v in wisdom.values()), default=0)


def entry_info(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    wisdom_path: Optional[pathlib.Path] = None,
) -> Optional[dict]:
    """Full stamped view of one wisdom entry: {"r", "gen", "ts"}, with
    legacy bare-int entries normalized to gen 0 / ts 0.0.  None when the
    key has never been tuned."""
    path = _wisdom_path(wisdom_path)
    wisdom = _load_cached(path)
    key = _key(_resolve_transform(transform, k, m), h, w, c_in, c_out)
    if key not in wisdom:
        return None
    v = wisdom[key]
    return {"r": _entry_r(v), "gen": _entry_gen(v), "ts": _entry_ts(v)}


_WISDOM_CACHE: dict = {}  # path -> (mtime_ns, parsed wisdom)


def _load_cached(path: pathlib.Path) -> dict:
    """mtime-validated wisdom read: `lookup_r` runs on every auto-dispatch
    plan, so it must not re-read and re-parse the file per call.  Writers
    (`tuned_r`) go through the uncached `_load` -- the atomic replace
    bumps mtime_ns, which invalidates this cache."""
    try:
        stamp = path.stat().st_mtime_ns
    except OSError:
        stamp = None
    key = str(path)
    hit = _WISDOM_CACHE.get(key)
    if hit is not None and hit[0] == stamp:
        return hit[1]
    wisdom = _load(path) if stamp is not None else {}
    _WISDOM_CACHE[key] = (stamp, wisdom)
    return wisdom


# TPU hardware models by `device_kind`, as JAX reports it
TPU_MODELS = {"TPU v5 lite": analysis.TPU_V5E}


def default_hw() -> analysis.HardwareModel:
    """Hardware model of device 0: the TPU model for its `device_kind`
    (a TPU missing from `TPU_MODELS` raises), the paper's SkylakeX
    machine on any other backend."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return analysis.SKYLAKE_X
    try:
        return TPU_MODELS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware model for TPU device_kind {dev.device_kind!r} "
            f"(known: {sorted(TPU_MODELS)})"
        ) from None


def feasible_candidates(
    c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    hw: Optional[analysis.HardwareModel] = None,
    candidates: Sequence[int] = _CANDIDATES,
) -> list:
    """Candidates within the private-memory upper bound; never empty --
    the smallest candidate survives even when the bound excludes all, so a
    degenerate geometry still tunes rather than erroring.  The bound is
    family-exact: complex FFT tiles halve the feasible R."""
    hw = hw or default_hw()
    tr = _resolve_transform(transform, k, m)
    r_max = analysis.max_r_ta(hw, c_in, c_out, tr.algebra)
    feas = [r for r in candidates if r <= r_max]
    return feas or [min(candidates)]


def predict_r(
    c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    hw: Optional[analysis.HardwareModel] = None,
    candidates: Sequence[int] = _CANDIDATES,
) -> int:
    """Analytic (non-measuring) R choice: the smallest feasible candidate
    at or above the R >= 2 CMR_fast lower bound, else the largest feasible
    one.  Used when tuning is disabled; `tuned_r` refines it by timing."""
    hw = hw or default_hw()
    feas = feasible_candidates(
        c_in, c_out, k=k, m=m, transform=transform, hw=hw,
        candidates=candidates,
    )
    target = analysis.min_r(hw)
    at_or_above = [r for r in feas if r >= target]
    return min(at_or_above) if at_or_above else max(feas)


def lookup_r(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    wisdom_path: Optional[pathlib.Path] = None,
    max_age_s: Optional[float] = None,
    min_gen: int = 0,
    now: Optional[float] = None,
) -> Optional[int]:
    """Non-measuring wisdom read: the tuned R for this transform family +
    layer geometry if a previous `tuned_r` pass stored one, else None.
    This is how ``algo="auto"`` benefits from the wisdom file without
    ever paying a measurement at dispatch time.

    Staleness-aware: with `max_age_s` set, entries whose timestamp is
    older than ``now - max_age_s`` read as absent (legacy unstamped
    entries have ts 0.0 and therefore always expire); with `min_gen`
    set, entries stamped with an older generation read as absent."""
    path = _wisdom_path(wisdom_path)
    wisdom = _load_cached(path)
    key = _key(_resolve_transform(transform, k, m), h, w, c_in, c_out)
    if key not in wisdom:
        return None
    v = wisdom[key]
    if _entry_gen(v) < min_gen:
        return None
    if max_age_s is not None:
        now = time.time() if now is None else now
        ts = _entry_ts(v)
        # an age bound only admits entries of KNOWN age: legacy
        # unstamped entries (ts 0.0) read as absent unconditionally
        if ts <= 0.0 or ts < now - max_age_s:
            return None
    return _entry_r(v)


def measure_r(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    batch: int = 1, candidates: Sequence[int] = _CANDIDATES, reps: int = 3,
) -> int:
    """Time the fused conv at each candidate R; return the fastest.
    Transform-generic: the timed loop is the shared tile engine driven by
    `transform` (Winograd F(m, k) by default)."""
    tr = _resolve_transform(transform, k, m)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, h, w, c_in)) * 0.1, jnp.float32)
    wk = jnp.asarray(
        rng.standard_normal((tr.k, tr.k, c_in, c_out)) * 0.1, jnp.float32
    )
    best_r, best_t = None, float("inf")
    for r in feasible_candidates(
        c_in, c_out, transform=tr, candidates=candidates
    ):
        fn = jax.jit(
            functools.partial(fused_tile_conv, transform=tr, pad=1, r_tiles=r)
        )
        jax.block_until_ready(fn(x, wk))  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, wk))
            ts.append(time.perf_counter() - t0)
        t = sorted(ts)[len(ts) // 2]
        if t < best_t:
            best_r, best_t = r, t
    return best_r if best_r is not None else min(candidates)


def tuned_r(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    wisdom_path: Optional[pathlib.Path] = None,
) -> int:
    """Cached best R for this transform family + layer geometry (measures
    on first use)."""
    tr = _resolve_transform(transform, k, m)
    path = _wisdom_path(wisdom_path)
    wisdom = _load(path)
    key = _key(tr, h, w, c_in, c_out)
    if key in wisdom:
        hit = _entry_r(wisdom[key])
        if hit is not None:  # blocks-only entries still need an R pass
            return hit
    r = measure_r(h, w, c_in, c_out, transform=tr)
    wisdom = _load(path)  # re-read: another tuner may have written meanwhile
    gen = max((_entry_gen(v) for v in wisdom.values()), default=0) + 1
    entry = {"r": int(r), "gen": gen, "ts": time.time()}
    prev_blocks = _entry_blocks(wisdom.get(key))
    if prev_blocks is not None:  # merge, don't clobber, the other dimension
        entry["blocks"] = prev_blocks.to_wisdom()
    wisdom[key] = entry
    atomic_write_text(path, json.dumps(wisdom, indent=1, sort_keys=True))
    return r


# ---------------------------------------------------------------------------
# Block-shape wisdom for the parametric tile engine (kernels.fused_tile).
#
# A tuned entry's "blocks" field serializes a BlockConfig -- tile rows R
# and tasks-per-program (0 = the matrix path's unchunked sweep) --
# alongside the scan engine's "r".  Both ride the same
# backend:family:geometry key and the same stamped {gen, ts} envelope, so
# atomic rewrites and staleness logic treat them as one entry.
# ---------------------------------------------------------------------------


def block_candidates(
    c_in: int, c_out: int,
    transform: transforms.Transform,
    hw: Optional[analysis.HardwareModel] = None,
) -> list:
    """Candidate block shapes: feasible R values crossed with the
    unchunked sweep (tpp=0, the CPU default) and a chunked variant that
    bounds the transform-domain working set (what wins once the tile
    population outgrows the shared level)."""
    cands = []
    for r in feasible_candidates(
        c_in, c_out, transform=transform, hw=hw, candidates=(8, 16, 24, 32)
    ):
        cands.append(BlockConfig(r=r, tasks_per_program=0))
        cands.append(BlockConfig(r=r, tasks_per_program=8))
    return cands


def _entry_blocks(value) -> Optional[BlockConfig]:
    if isinstance(value, dict) and "blocks" in value:
        return BlockConfig.from_wisdom(value["blocks"])
    return None


def lookup_blocks(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    wisdom_path: Optional[pathlib.Path] = None,
) -> Optional[BlockConfig]:
    """Non-measuring read of the tuned block shape, None when untuned.
    Like `lookup_r`, this is the dispatch-time path: planning consults it
    on every auto plan and must never pay a measurement."""
    path = _wisdom_path(wisdom_path)
    wisdom = _load_cached(path)
    key = _key(_resolve_transform(transform, k, m), h, w, c_in, c_out)
    return _entry_blocks(wisdom.get(key))


def measure_blocks(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    batch: int = 1,
    candidates: Optional[Sequence[BlockConfig]] = None,
    reps: int = 3,
    backend: Optional[str] = None,
) -> BlockConfig:
    """Time the parametric tile engine at each candidate block shape on
    the real geometry; return the fastest.  `backend` overrides the
    engine backend (e.g. "pallas_interpret" so CPU CI tunes the exact
    kernel the accelerator runs)."""
    from repro.kernels import fused_tile as _ft

    tr = _resolve_transform(transform, k, m)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, h, w, c_in)) * 0.1, jnp.float32)
    wk = jnp.asarray(
        rng.standard_normal((tr.k, tr.k, c_in, c_out)) * 0.1, jnp.float32
    )
    cands = list(candidates or block_candidates(c_in, c_out, tr))
    best, best_t = cands[0], float("inf")
    for blocks in cands:
        fn = jax.jit(
            functools.partial(
                _ft.conv2d_fused_tile, transform=tr, pad=1,
                blocks=blocks, backend=backend,
            )
        )
        try:
            jax.block_until_ready(fn(x, wk))  # compile
        except _ft.UnsupportedSpec:
            continue
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, wk))
            ts.append(time.perf_counter() - t0)
        t = sorted(ts)[len(ts) // 2]
        if t < best_t:
            best, best_t = blocks, t
    return best


def tuned_blocks(
    h: int, w: int, c_in: int, c_out: int, *, k: int = 3, m: int = 5,
    transform: Optional[transforms.Transform] = None,
    wisdom_path: Optional[pathlib.Path] = None,
    backend: Optional[str] = None,
) -> BlockConfig:
    """Cached best block shape for this family + geometry (measures on
    first use).  Merges into the existing stamped entry -- a prior tuned
    R survives, and a concurrent tuner's writes are re-read before the
    atomic replace, mirroring `tuned_r`."""
    tr = _resolve_transform(transform, k, m)
    path = _wisdom_path(wisdom_path)
    key = _key(tr, h, w, c_in, c_out)
    hit = _entry_blocks(_load(path).get(key))
    if hit is not None:
        return hit
    blocks = measure_blocks(
        h, w, c_in, c_out, transform=tr, backend=backend
    )
    wisdom = _load(path)  # re-read: another tuner may have written meanwhile
    gen = max((_entry_gen(v) for v in wisdom.values()), default=0) + 1
    prev = wisdom.get(key)
    prev_r = _entry_r(prev) if prev is not None else None
    wisdom[key] = {
        "r": prev_r if prev_r is not None else int(blocks.r),
        "blocks": blocks.to_wisdom(),
        "gen": gen,
        "ts": time.time(),
    }
    atomic_write_text(path, json.dumps(wisdom, indent=1, sort_keys=True))
    return blocks


# ---------------------------------------------------------------------------
# Roofline calibration (one-shot GEMM / stream microbenchmark).
#
# The hardcoded paper machines (SKYLAKE_X et al.) describe 18-core AVX512
# boxes; on the actual host they can be orders of magnitude off, which
# turns `measured_over_predicted` into noise and poisons fusion-group
# decisions.  One measured {peak_flops, dram_bw} pair per backend, cached
# in the wisdom file under "calib:{backend}", anchors every roofline
# number to the machine the benchmarks actually run on.
# ---------------------------------------------------------------------------

_CALIB_PREFIX = "calib"
_CALIB_GEMM_N = 768
_CALIB_STREAM_MB = 32


def _calib_key() -> str:
    return f"{_CALIB_PREFIX}:{jax.default_backend()}"


def _time_best(fn, *args, reps: int = 5) -> float:
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run_calibration() -> dict:
    """Measure achievable {peak_flops, dram_bw} on this host: a dense
    f32 GEMM for the compute roof, a big-array copy (read + write) for
    the memory roof.  Seconds to run, cached by `measure_calibration`."""
    n = _CALIB_GEMM_N
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)) * 0.1, jnp.float32)
    t_gemm = _time_best(jax.jit(jnp.matmul), a, b)
    peak = 2.0 * n**3 / t_gemm
    m = _CALIB_STREAM_MB * 2**20 // 4
    x = jnp.ones((m,), jnp.float32)
    t_stream = _time_best(jax.jit(lambda v: v * 1.0001 + 0.5), x)
    bw = 2.0 * 4 * m / t_stream  # one read + one write per element
    return {"peak_flops": float(peak), "dram_bw": float(bw)}


def lookup_calibration(
    wisdom_path: Optional[pathlib.Path] = None,
) -> Optional[dict]:
    """Cached calibration for the current backend, None when never run."""
    entry = _load_cached(_wisdom_path(wisdom_path)).get(_calib_key())
    return dict(entry) if isinstance(entry, dict) else None


def measure_calibration(
    wisdom_path: Optional[pathlib.Path] = None, *, refresh: bool = False,
) -> dict:
    """Calibration with wisdom caching: measures once per backend per
    wisdom file, then serves the stamped cache (refresh=True re-runs)."""
    path = _wisdom_path(wisdom_path)
    if not refresh:
        hit = lookup_calibration(path)
        if hit is not None:
            return hit
    entry = run_calibration()
    wisdom = _load(path)
    gen = max((_entry_gen(v) for v in wisdom.values()), default=0) + 1
    entry = {**entry, "gen": gen, "ts": time.time()}
    wisdom[_calib_key()] = entry
    atomic_write_text(path, json.dumps(wisdom, indent=1, sort_keys=True))
    return entry
