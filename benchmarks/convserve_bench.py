"""convserve engine benchmark: planned nets vs all-direct, cold vs warm,
fused vs unfused -- with a machine-readable JSON artifact.

Per net (the mixed-channel VGG and the stride-2 ResNet-style
downsampling net), CSV rows:

  convserve/<net>/plan    -- plan_net wall time (pure roofline model)
  convserve/<net>/cold    -- first wave: jit compile + kernel transforms
  convserve/<net>/warm    -- steady-state serving time, cache hot
  convserve/<net>/unfused -- same plan with fusion groups stripped
  convserve/<net>/direct  -- the same net all-direct (vendor baseline)
  convserve/<net>/stage/* -- per-stage wall times (separately jitted)

and everything lands in ``BENCH_convserve.json`` (per-net, per-stage
wall times + cache hit rates) so the perf trajectory is tracked across
PRs.

    PYTHONPATH=src python -m benchmarks.convserve_bench

`smoke=True` (the CI path, `benchmarks.run --smoke`) runs the tiny test
net at a tiny geometry and asserts fused == unfused == direct numerical
parity: it exists to catch dispatcher and fusion regressions that only
bite at execution time, not to produce meaningful numbers.
"""

from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn, time_pair
from repro.configs.convnets import (
    fft_fewchannel,
    resnet_downsample,
    tiny_testnet,
    vgg_mixed_channel,
)
from repro.convserve import Engine, init_weights, run_direct
from repro.convserve.obs import roofline as roofline_mod
from repro.convserve.planner import predict_stage_times
from repro.core import analysis, transforms, tune

BENCH_PATH = pathlib.Path("BENCH_convserve.json")

_HW: list = []  # one-shot cache of the calibrated model for this run


def bench_hw() -> analysis.HardwareModel:
    """The calibrated hardware model every bench number is predicted
    against: the device's model (`tune.default_hw`) with compute/memory
    roofs replaced by the measured GEMM/stream microbenchmark (cached in
    the wisdom file, so repeat runs pay nothing).  Uncalibrated peaks on
    an arbitrary host made `measured_over_predicted` pure noise
    (80-440x); calibration is what makes the divergence signal usable."""
    if not _HW:
        _HW.append(analysis.calibrated_hw())
    return _HW[0]


def profile_stage_rows(net, x, hw) -> list:
    """Measured AND roofline-predicted seconds per stage -- the
    predicted-vs-measured delta is the cost-model divergence the adapt
    loop (convserve.adapt) acts on, surfaced in the bench artifact.
    Modeled stage times are per image; the measured pass runs the whole
    batch, so predictions are scaled by x's leading dim to compare
    like with like."""
    batch = int(x.shape[0])
    predicted = dict(predict_stage_times(net.program, hw))
    profile = list(net.profile_stages(x))
    rows = []
    for label, secs in profile:
        pred = predicted[label] * batch
        rows.append(
            {
                "label": label,
                "us": secs * 1e6,
                "predicted_us": pred * 1e6,
                "measured_over_predicted": (
                    secs / pred if pred > 0 else None
                ),
            }
        )
    return rows, profile


def bench_net(spec, batch: int, side: int, c_in: int, record: dict) -> None:
    ws = init_weights(spec, seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, side, side, c_in)) * 0.1, jnp.float32
    )
    engine = Engine(hw=bench_hw())

    t0 = time.perf_counter()
    net = engine.compile(spec, ws, input_hw=(side, side))
    t_plan = time.perf_counter() - t0
    algos = ";".join(net.plan.algos())
    print(row(f"convserve/{spec.name}/plan", t_plan * 1e6, algos))

    t0 = time.perf_counter()
    jax.block_until_ready(net(x))
    t_cold = time.perf_counter() - t0
    print(row(f"convserve/{spec.name}/cold", t_cold * 1e6, f"batch{batch}"))

    # fused vs unfused interleaved (time_pair): the two programs differ
    # only in stage structure, so separate measurement windows would
    # compare load drift, not fusion
    unfused = engine.compile(spec, ws, input_hw=(side, side), fuse=False)
    t_warm, t_unfused = time_pair(net, unfused, x)
    cache = net.cache.stats()
    print(
        row(
            f"convserve/{spec.name}/warm", t_warm * 1e6,
            f"{t_warm * 1e3 / batch:.1f}ms/img;hits{cache['hits']}",
        )
    )
    print(
        row(
            f"convserve/{spec.name}/unfused", t_unfused * 1e6,
            f"{net.program.n_fused}groups",
        )
    )

    vendor = jax.jit(lambda x: run_direct(spec, ws, x))
    t_dir = time_fn(vendor, x)
    print(
        row(
            f"convserve/{spec.name}/direct", t_dir * 1e6,
            f"{t_dir * 1e3 / batch:.1f}ms/img",
        )
    )

    stages, profile = profile_stage_rows(net, x, engine.hw)
    for st in stages:
        print(
            row(
                f"convserve/{spec.name}/stage/{st['label']}", st["us"],
                f"pred{st['predicted_us']:.0f}us;"
                f"x{st['measured_over_predicted']:.2f}",
            )
        )

    record[spec.name] = {
        "algos": net.plan.algos(),
        "fusion_groups": [list(g.layers) for g in net.plan.groups],
        "plan_us": t_plan * 1e6,
        "cold_us": t_cold * 1e6,
        "warm_us": t_warm * 1e6,
        "warm_us_per_img": t_warm * 1e6 / batch,
        "unfused_warm_us": t_unfused * 1e6,
        "direct_us": t_dir * 1e6,
        "stages": stages,
        "roofline": roofline_mod.roofline_section(
            net.program, profile, engine.hw, batch=batch
        ),
        "cache": net.cache.stats(),
    }


def bench_fft_net(
    batch: int, side: int, record: dict, *, iters: int = 30
) -> None:
    """The FFT-selected few-channel net: the transform the planner picks
    when tiles are DRAM-bound (Zlateski et al.'s claim through our
    roofline), served as one FFT-backed fusion group.

    Asserts the plan (all fft_fused + >= 1 group) and fused-vs-direct
    parity, then times fused vs unfused interleaved (`time_pair`): the
    pair differ only in stage structure, so back-to-back medians would
    measure load drift, not fusion.
    """
    spec = fft_fewchannel(4)
    ws = init_weights(spec, seed=0)
    # block-autotune both engine families at this net's layer geometries
    # before planning: lookup_blocks then resolves at plan time and the
    # auto ranking prices the tuned engine (analysis.engine_cost_ta)
    # instead of the static idealization.  Repeat runs hit the stamped
    # wisdom entries and pay nothing.
    for c_in, c_out in sorted(
        {(l.c_in, l.c_out) for l in spec.layers if l.kind == "conv"}
    ):
        for tr in (
            transforms.WinogradTransform(m=5, k=3),
            transforms.FFTTransform(t=16, k=3),
        ):
            tune.tuned_blocks(side, side, c_in, c_out, transform=tr)
    engine = Engine(hw=bench_hw())
    fused = engine.compile(spec, ws, input_hw=(side, side))
    unfused = engine.compile(spec, ws, input_hw=(side, side), fuse=False)
    # every layer must resolve to a *fused transformed* realization; the
    # family is the calibrated cost model's call (the paper: FFT wins at
    # high channel counts, Winograd at few), so the gate is deliberately
    # family-agnostic -- the FFT family's parity is pinned by the
    # interpret-mode kernel matrix in tests/test_fused_tile.py
    fused_algos = {"fft_fused", "l3_fused"}
    assert all(a in fused_algos for a in fused.plan.algos()), (
        f"few-channel net did not plan fused transforms: {fused.plan.algos()}"
    )
    assert fused.program.n_fused >= 1, (
        f"FFT net planned no fusion groups: {fused.describe()}"
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, side, side, 4)) * 0.1, jnp.float32
    )
    ref = run_direct(spec, ws, x)
    scale = float(jnp.abs(ref).max())
    rel_fused = float(jnp.abs(fused(x) - ref).max()) / scale
    rel_pair = float(jnp.abs(fused(x) - unfused(x)).max()) / scale
    assert rel_fused < 1e-3, f"FFT fused vs direct diverged: {rel_fused}"
    assert rel_pair < 1e-4, f"FFT fused vs unfused diverged: {rel_pair}"

    t_fused, t_unfused = time_pair(fused, unfused, x, iters=iters)
    vendor = jax.jit(lambda x: run_direct(spec, ws, x))
    t_dir = time_fn(vendor, x)
    print(row(f"convserve/{spec.name}/warm", t_fused * 1e6,
              ";".join(fused.plan.algos())))
    print(row(f"convserve/{spec.name}/unfused", t_unfused * 1e6,
              f"{fused.program.n_fused}groups"))
    print(row(f"convserve/{spec.name}/direct", t_dir * 1e6))
    print(row(f"convserve/{spec.name}/fused_vs_direct", 0.0,
              f"rel{rel_fused:.2e}"))
    stages, profile = profile_stage_rows(fused, x, engine.hw)
    for st in stages:
        print(
            row(
                f"convserve/{spec.name}/stage/{st['label']}", st["us"],
                f"pred{st['predicted_us']:.0f}us;"
                f"x{st['measured_over_predicted']:.2f}",
            )
        )
    record[spec.name] = {
        "algos": fused.plan.algos(),
        "fusion_groups": [list(g.layers) for g in fused.plan.groups],
        "warm_us": t_fused * 1e6,
        "unfused_warm_us": t_unfused * 1e6,
        "direct_us": t_dir * 1e6,
        "fused_vs_direct_rel": rel_fused,
        "fused_vs_unfused_rel": rel_pair,
        "stages": stages,
        "roofline": roofline_mod.roofline_section(
            fused.program, profile, engine.hw, batch=batch
        ),
        "cache": fused.cache.stats(),
    }


def _smoke(record: dict) -> None:
    """Tiny geometry, full pipeline: a fused plan and its unfused strip
    must agree with the direct oracle (fusion-group parity gate)."""
    spec = tiny_testnet(4)
    ws = init_weights(spec, seed=0)
    engine = Engine(hw=bench_hw())
    fused = engine.compile(spec, ws, input_hw=(16, 16))
    unfused = engine.compile(spec, ws, input_hw=(16, 16), fuse=False)
    # without this the parity gate is vacuous: a planner regression that
    # stops fusing would compare two identical unfused programs
    assert fused.program.n_fused >= 1, (
        f"smoke net planned no fusion groups: {fused.describe()}"
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 16, 16, 4)) * 0.1, jnp.float32)
    ref = run_direct(spec, ws, x)
    scale = float(jnp.abs(ref).max())
    rel_fused = float(jnp.abs(fused(x) - ref).max()) / scale
    rel_pair = float(jnp.abs(fused(x) - unfused(x)).max()) / scale
    print(row("convserve/smoke/fused_vs_direct", 0.0, f"rel{rel_fused:.2e}"))
    print(row("convserve/smoke/fused_vs_unfused", 0.0, f"rel{rel_pair:.2e}"))
    assert rel_fused < 1e-3, f"fused vs direct diverged: {rel_fused}"
    assert rel_pair < 1e-4, f"fused vs unfused diverged: {rel_pair}"
    record[spec.name] = {
        "smoke": True,
        "fused_vs_direct_rel": rel_fused,
        "fused_vs_unfused_rel": rel_pair,
        "fusion_groups": [list(g.layers) for g in fused.plan.groups],
        "cache": fused.cache.stats(),
    }


def main(batch: int = 2, side: int = 64, smoke: bool = False) -> None:
    record: dict = {}
    try:
        if smoke:  # CI: tiny geometry, fusion parity under time pressure
            _smoke(record)
            # the FFT-selected few-channel net, small geometry: asserts
            # the transform choice + FFT fusion-group parity, and records
            # the fused-vs-unfused warm pair
            bench_fft_net(batch, 48, record, iters=20)
        else:
            bench_net(
                vgg_mixed_channel(c_in=3), batch, side, c_in=3, record=record
            )
            bench_net(
                resnet_downsample(c_in=3), batch, side, c_in=3, record=record
            )
            bench_fft_net(batch, side, record)
    finally:
        # partial results still land on disk (and in the CI artifact)
        # when a parity gate fires mid-run
        hw = bench_hw()
        BENCH_PATH.write_text(
            json.dumps(
                {
                    "bench": "convserve",
                    "schema_version": roofline_mod.SCHEMA_VERSION,
                    "smoke": smoke,
                    "calibration": {
                        "hw": hw.name,
                        "peak_flops": hw.peak_flops,
                        "dram_bw": hw.dram_bw,
                    },
                    "nets": record,
                },
                indent=1,
                sort_keys=True,
            )
        )
        print(f"# wrote {BENCH_PATH}")


if __name__ == "__main__":
    main()
