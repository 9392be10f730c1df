#!/usr/bin/env python3
"""Bring-up smoke of the served path on a TPU: vgg-mixed at 224 px.

Drives the path a user calls -- `Engine` plans, `ReplicaPool.build`
compiles, `ServeRuntime` admits, batches and serves -- once, at
vgg-mixed's full widths (64/128/256 channels, 3 input channels) on
classification-resolution images in waves of up to 8, with weights made
from a seed.  Every served output is checked against the plain
direct-convolution reference (`run_direct`) at `highest` matmul
precision.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # only one wave sharded over 4 chips

Phases, in order: device (a TPU or a non-zero exit), cache (JAX's
persistent compilation cache), wisdom (a fresh file, so the plan comes
from committed code), plan (per-layer algorithm and tile backend; the
wave program must hold a compiled Pallas kernel per transformed layer),
serve (16 requests, 4 of them smaller than the bucket) and check.  Any
failed phase exits non-zero.  The last line of a passing run is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.  Longer
records go to chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

SIDE = 224  # classification resolution: the one serving bucket
MAX_BATCH = 8
SEED = 0
# 12 full-bucket requests and 4 smaller ones, so extent masking runs
SIZES = (SIDE,) * 12 + (160, 200, 160, 200)
# The repo's bound for a transformed net against the direct reference
# (examples/convnet_l3fusion.py): max |y - ref| / max |ref| per image.
# A float32 transformed path holds it; a path whose GEMMs round their
# inputs to bfloat16 does not, and must not pass here.
REL_TOL = 1e-3
# Sharded rows against the one-chip wave: the same float32 math at a
# smaller batch, equal to rounding (the fleet tests' bound).
SHARD_TOL = 1e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def phase_device(want: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(
        f"[device] platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)}",
        flush=True,
    )
    if d0.platform != "tpu":
        fail(
            f"no TPU: JAX found platform {d0.platform!r}; this smoke "
            "runs only on the chip and never falls back to the CPU"
        )
    if len(devs) < want:
        fail(f"needs {want} TPU chips, JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def images(sizes, c, seed=SEED):
    rng = np.random.default_rng(seed)
    return [
        (rng.standard_normal((s, s, c)) * 0.1).astype(np.float32)
        for s in sizes
    ]


def rel_err(y, ref) -> float:
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def phase_plan(engine, spec, weights):
    """Plan at the bucket, lower one full wave and report per layer the
    algorithm, params and tile backend its kernel dispatch resolved to
    (a Pallas tile kernel is named `convserve_tile_<family>_t<T>` in
    the lowered wave program)."""
    from repro.convserve import ReplicaPool, plan_net
    from repro.core import registry
    from repro.kernels.fused_tile import ops as tile_ops

    if os.environ.get(tile_ops._ENV_BACKEND):
        fail(f"{tile_ops._ENV_BACKEND} is set: the smoke runs the default")
    backend = tile_ops.resolve_backend()
    plan = plan_net(spec, SIDE, SIDE, hw=engine.hw)
    pool = ReplicaPool.build(engine, spec, weights, n=1, plan=plan)
    t0 = time.perf_counter()
    c0 = spec.conv_layers()[0][1].c_in
    lowered = pool.executors[0].lower(
        np.zeros((MAX_BATCH, SIDE, SIDE, c0), np.float32),
        np.full((MAX_BATCH, 2), SIDE, np.int32),
    )
    lower_s = time.perf_counter() - t0
    text = lowered.as_text()
    named = set(re.findall(r"convserve_tile_([a-z0-9]+)_t[0-9]+", text))
    tiled = [
        p for p in plan.layers if registry.get(p.algo).chain_family
    ]
    families = set()
    print(f"[plan] {spec.name} at {SIDE}x{SIDE} on {engine.hw.name}; "
          f"fusion groups {[g.layers for g in plan.groups]}")
    for p in plan.layers:
        s = p.spec
        resolved = "xla"
        if p in tiled:
            family = registry.get(p.algo).tile_algebra(p.algo_plan()).family
            families.add(family)
            resolved = "pallas" if family in named else backend
        print(f"[plan]   layer {p.layer:2d} {s.h:3d}px {s.c_in:3d}->"
              f"{s.c_out:<3d} {p.algo:10s} {p.params} backend={resolved}")
    calls = text.count("tpu_custom_call")
    print(f"[plan] {len(tiled)} transformed layers, named tile kernels "
          f"{sorted(named)}, {calls} tpu_custom_call in the wave program")
    print(f"[plan] kernel transforms prepared and wave program (batch "
          f"{MAX_BATCH}) traced and lowered in {lower_s:.2f}s")
    if tiled and backend != "pallas":
        fail(f"transformed layers resolved to {[backend]}")
    if families - named or calls < len(tiled):
        fail("a transformed layer does not run the compiled Pallas kernel")
    return plan, pool, lower_s


def phase_serve(pool, spec, imgs):
    from repro.convserve import RuntimeConfig, ServeRuntime

    rt = ServeRuntime(pool, RuntimeConfig(max_batch=MAX_BATCH,
                                          buckets=(SIDE,)))
    t0 = time.perf_counter()
    rt.warmup()
    warm_s = time.perf_counter() - t0
    print(f"[serve] warm-up: wave program (batch {MAX_BATCH}) compiled and "
          f"run in {warm_s:.2f}s", flush=True)
    t0 = time.perf_counter()
    for rid, img in enumerate(imgs):
        rt.submit(img, rid=rid)
    rt.drain()
    serve_s = time.perf_counter() - t0
    counters = rt.stats()["counters"]
    rt.shutdown()
    n_err = counters.get("wave_errors", 0)
    print(f"[serve] {len(rt.results)}/{len(imgs)} served in {serve_s:.2f}s, "
          f"{len(rt.rejections)} rejected, {n_err} wave errors, "
          f"{counters.get('waves', 0)} waves")
    for e in rt.errors:
        print(f"[serve] wave error: {type(e).__name__}: {e}",
              file=sys.stderr)
    if len(rt.results) != len(imgs) or rt.rejections or rt.errors or n_err:
        fail("not every request was served cleanly")
    return rt.results, {"warmup_s": warm_s, "serve_s": serve_s}


def phase_check(engine, spec, weights, imgs, results):
    """Every served output against `run_direct` at highest precision;
    the all-direct plan's error is printed beside it."""
    import jax
    import jax.numpy as jnp

    from repro.convserve import plan_net, run_direct

    ref_fn = jax.jit(lambda x: run_direct(spec, weights, x))
    direct = engine.compile(
        spec, weights,
        plan=plan_net(spec, SIDE, SIDE, hw=engine.hw, allowed=("direct",)),
    )
    c0 = imgs[0].shape[-1]
    errs = []
    for lo in range(0, len(imgs), MAX_BATCH):
        wave = imgs[lo:lo + MAX_BATCH]
        batch = np.zeros((len(wave), SIDE, SIDE, c0), np.float32)
        for i, im in enumerate(wave):
            batch[i, :im.shape[0], :im.shape[1]] = im
        ext = np.array([im.shape[:2] for im in wave], np.int32)
        y_direct = np.asarray(direct(batch, ext))
        for i, im in enumerate(wave):
            rid = lo + i
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(ref_fn(jnp.asarray(im)[None]))[0]
            oh, ow = ref.shape[:2]
            e_plan = rel_err(results[rid], ref)
            e_direct = rel_err(y_direct[i, :oh, :ow], ref)
            errs.append({"rid": rid, "side": im.shape[0], "planned": e_plan,
                         "all_direct": e_direct})
            print(f"[check] image {rid:2d} {im.shape[0]}px rel err: planned "
                  f"{e_plan:.3e}  all-direct {e_direct:.3e}")
    worst = max(e["planned"] for e in errs)
    print(f"[check] worst planned rel err {worst:.3e} (bound {REL_TOL:g})")
    if worst > REL_TOL:
        fail(f"planned net misses the {REL_TOL:g} bound: {worst:.3e}")
    return errs


def one_chip(dev: dict) -> dict:
    from repro.compile_cache import enable_compile_cache

    cache = pathlib.Path(enable_compile_cache())
    n_before = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"[cache] {cache} ({n_before} entries before this run)")

    wisdom = OUT_DIR / "wisdom.json"
    wisdom.unlink(missing_ok=True)
    os.environ["REPRO_WISDOM"] = str(wisdom)
    print(f"[wisdom] fresh file {wisdom}")

    from repro.configs.convnets import vgg_mixed_channel
    from repro.convserve import Engine, init_weights

    spec = vgg_mixed_channel(c_in=3)
    weights = init_weights(spec, seed=SEED)
    engine = Engine()
    plan, pool, lower_s = phase_plan(engine, spec, weights)
    imgs = images(SIZES, spec.conv_layers()[0][1].c_in)
    results, times = phase_serve(pool, spec, imgs)
    errs = phase_check(engine, spec, weights, imgs, results)
    return {"device": dev, "cache_entries_before": n_before,
            "lower_s": lower_s, **times,
            "plan": json.loads(plan.to_json()), "errors": errs}


def four_chips(dev: dict) -> dict:
    """One wave of 8 at 224 px, sharded over a 4-device data mesh, against
    the same net on one chip."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.configs.convnets import vgg_mixed_channel
    from repro.convserve import Engine, init_weights
    from repro.convserve.fleet import ShardedWaveExecutor

    enable_compile_cache()
    os.environ["REPRO_WISDOM"] = str(OUT_DIR / "wisdom4.json")
    (OUT_DIR / "wisdom4.json").unlink(missing_ok=True)
    spec = vgg_mixed_channel(c_in=3)
    weights = init_weights(spec, seed=SEED)
    net = Engine().compile(spec, weights, input_hw=(SIDE, SIDE))
    x = np.stack(images((SIDE,) * MAX_BATCH, 3))
    ext = np.full((MAX_BATCH, 2), SIDE, np.int32)
    t0 = time.perf_counter()
    y1 = np.asarray(net(x, ext))
    t1 = time.perf_counter() - t0
    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    sharded = ShardedWaveExecutor(net, shards=4, mesh=mesh)
    t0 = time.perf_counter()
    ys = jax.block_until_ready(sharded(x, ext))
    t4 = time.perf_counter() - t0
    n_dev = len(ys.sharding.device_set)
    diff = float(np.abs(np.asarray(ys) - y1).max())
    scale = float(np.abs(y1).max())
    print(f"[four-chips] one chip: {t1:.2f}s (cold); sharded: {t4:.2f}s "
          f"(cold); output on {n_dev} devices")
    print(f"[four-chips] max |sharded - one chip| = {diff:.3e} "
          f"(scale {scale:.3e}, bound {SHARD_TOL:g} x scale)")
    if n_dev != 4:
        fail(f"sharded output spans {n_dev} devices, not 4")
    if diff > SHARD_TOL * scale:
        fail("sharded wave does not match the one-chip wave")
    return {"device": dev, "max_abs_diff": diff, "scale": scale,
            "devices": n_dev, "one_chip_s": t1, "sharded_s": t4}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-wave phase")
    args = ap.parse_args(argv)
    dev = phase_device(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    record = four_chips(dev) if args.four_chips else one_chip(dev)
    name = "four_chips.json" if args.four_chips else "one_chip.json"
    (OUT_DIR / name).write_text(json.dumps(record, indent=1))
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
