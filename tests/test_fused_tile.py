"""The parametric tile engine (`repro.kernels.fused_tile`): one kernel
parity matrix across transform families x engine scenarios x backends,
the three-stage structure through the same `TileKernelSpec`, block-shape
wisdom surviving tune.py's atomic rewrites, and the calibration cache.

Exactness oracle is always `lax.conv_general_dilated` to fp32 transform
tolerance.  The Pallas column runs in interpreter mode (CPU CI has no
TPU); the dedicated `pallas-interpret` CI job re-runs this file with
`REPRO_TILE_BACKEND=pallas_interpret` so the dispatch-level paths take
the kernel too.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analysis, pipeline, registry, tiling, transforms, tune
from repro.core.registry import ConvSpec
from repro.kernels.fused_tile import (
    BlockConfig,
    conv2d_fused_tile,
    engine_supported,
    resolve_backend,
    staged_matrix_fns,
)

BIG_HW = analysis.HardwareModel(
    name="big", peak_flops=1e12, dram_bw=1e11, fast_shared_bw=5e11,
    fast_shared_bytes=1 << 30, private_bytes=1 << 24,
)

FAMILIES = (
    transforms.WinogradTransform(m=3, k=3),  # T=5
    transforms.FFTTransform(t=8, k=3),  # complex re/im split planes
)

BACKENDS = ("xla", "pallas_interpret")

SCENARIOS = (
    "plain", "stride2", "grouped", "ragged", "bias_relu", "chunked",
)


def _lax_ref(x, w, pad=0, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def _rel(y, ref):
    return float(
        jnp.abs(y.astype(jnp.float32) - ref.astype(jnp.float32)).max()
        / (jnp.abs(ref.astype(jnp.float32)).max() + 1e-9)
    )


# ---------------------------------------------------- the parity matrix


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("tr", FAMILIES, ids=lambda t: t.family)
def test_kernel_parity_matrix(tr, scenario, backend):
    """Both transform families run the one parametric kernel on both
    engine backends and agree with the direct conv in every scenario."""
    rng = np.random.default_rng(11)
    groups = 2 if scenario == "grouped" else 1
    b, h, w, c_in, c_out = 2, 14, 14, 4, 4
    if scenario == "ragged":  # extents not a tile-grid multiple
        h, w = 13, 11
    x = jnp.asarray(rng.standard_normal((b, h, w, c_in)) * 0.1, jnp.float32)
    wk = jnp.asarray(
        rng.standard_normal((3, 3, c_in // groups, c_out)) * 0.1,
        jnp.float32,
    )
    assert engine_supported(tr, x.dtype)

    blocks = None
    if scenario == "chunked":  # bounded-working-set sweep (tpp > 0)
        blocks = BlockConfig(r=2, tasks_per_program=2)
    epilogue = None
    ref = _lax_ref(x, wk, pad=1, groups=groups)
    if scenario == "bias_relu":
        bvec = jnp.asarray(rng.standard_normal(c_out) * 0.1, jnp.float32)
        epilogue = registry.ElementwiseOps((("bias", bvec), ("relu",)))
        ref = jax.nn.relu(ref + bvec)

    y = conv2d_fused_tile(
        x, wk, tr, pad=1, blocks=blocks, groups=groups,
        epilogue=epilogue, backend=backend,
    )
    if scenario == "stride2":  # engine is stride-1 + decimation
        y = registry.decimate(y, 2)
        ref = _lax_ref(x, wk, pad=1, stride=2, groups=groups)
    assert y.shape == ref.shape, (tr.family, scenario, backend)
    assert _rel(y, ref) < 5e-5, (tr.family, scenario, backend)


@pytest.mark.parametrize("tr", FAMILIES, ids=lambda t: t.family)
def test_three_stage_through_same_spec(tr):
    """The materializing three-stage structure consumes the same
    `TileKernelSpec` as the fused kernel and stays exact -- all four
    transformed algorithms now share one parametric code path."""
    rng = np.random.default_rng(5)
    b, h, w, c_in, c_out = 2, 12, 12, 3, 5
    x = jnp.asarray(rng.standard_normal((b, h, w, c_in)) * 0.1, jnp.float32)
    wk = jnp.asarray(
        rng.standard_normal((3, 3, c_in, c_out)) * 0.1, jnp.float32
    )
    spec = tr.kernel_spec()
    assert spec is not None
    plan = tiling.TilePlan.build(h, w, tr.k, 1, tr.t)
    s1, s2, s3 = staged_matrix_fns(plan, spec)
    xp = tiling.pad_input(x, plan)
    wt = tr.kernel_transform(wk)  # family-native cached form
    y = s3(s2(s1(xp), wt), b).astype(x.dtype)
    ref = _lax_ref(x, wk, pad=1)
    assert y.shape == ref.shape
    assert _rel(y, ref) < 5e-5, tr.family


@pytest.mark.parametrize("backend", BACKENDS)
def test_two_link_fusion_group_epilogues(backend):
    """A two-link chain with bias+relu glue folded into each link's
    scatter phase equals the composed direct convs -- the engine form of
    a planned fusion group's interior."""
    rng = np.random.default_rng(7)
    tr = transforms.WinogradTransform(m=3, k=3)
    x = jnp.asarray(rng.standard_normal((2, 12, 12, 2)) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((3, 3, 2, 3)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((3, 3, 3, 3)) * 0.1, jnp.float32)
    b1 = jnp.asarray(rng.standard_normal(3) * 0.1, jnp.float32)
    ep = registry.ElementwiseOps((("bias", b1), ("relu",)))
    mid = conv2d_fused_tile(x, w1, tr, pad=1, epilogue=ep, backend=backend)
    y = conv2d_fused_tile(mid, w2, tr, pad=1, backend=backend)
    ref_mid = jax.nn.relu(_lax_ref(x, w1, pad=1) + b1)
    ref = _lax_ref(ref_mid, w2, pad=1)
    assert _rel(y, ref) < 5e-5


def test_backend_resolution_order(monkeypatch):
    """Explicit argument > REPRO_TILE_BACKEND env > platform default."""
    monkeypatch.delenv("REPRO_TILE_BACKEND", raising=False)
    default = resolve_backend(None)
    assert default in ("xla", "pallas")
    monkeypatch.setenv("REPRO_TILE_BACKEND", "scan")
    assert resolve_backend(None) == "scan"
    assert resolve_backend("xla") == "xla"  # explicit wins over env
    monkeypatch.setenv("REPRO_TILE_BACKEND", "bogus")
    with pytest.raises(ValueError):
        resolve_backend(None)


def test_interpreter_refused_on_tpu(monkeypatch):
    """On a TPU the interpreter never stands in for the compiled kernel,
    whether asked for by argument or by the env override."""
    from repro.kernels.fused_tile import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_TILE_BACKEND", raising=False)
    assert resolve_backend(None) == "pallas"
    with pytest.raises(ValueError, match="interpret"):
        resolve_backend("pallas_interpret")
    monkeypatch.setenv("REPRO_TILE_BACKEND", "pallas_interpret")
    with pytest.raises(ValueError, match="interpret"):
        resolve_backend(None)


@pytest.mark.parametrize(
    "c_in,c_out,fits",
    [(128, 128, True), (256, 256, False), (64, 130, False), (3, 64, True)],
)
def test_planner_excludes_what_the_kernel_cannot_hold(c_in, c_out, fits):
    """A TPU plan takes a transformed family only where the Pallas
    kernel can lay the layer out (channels fill one partial or whole
    lane tiles) within its VMEM budget: FFT 256->256's stationary
    right-hand matrices alone are ~75 MB."""
    from repro.kernels.fused_tile import kernel

    tr = transforms.FFTTransform(t=16, k=3)
    assert kernel.kernel_fits(tr.kernel_spec(), c_in, c_out, 8) == fits
    spec = ConvSpec(h=56, w=56, c_in=c_in, c_out=c_out, k=3, pad=1)
    ap = registry.get("fft_fused").plan(spec, analysis.TPU_V5E)
    assert np.isfinite(ap.cost) == fits
    # the same layer on a model without the kernel is unaffected
    assert np.isfinite(registry.get("fft_fused").plan(spec, BIG_HW).cost)


@pytest.mark.parametrize("tpp,fits", [(0, True), (1, True), (16, False)])
def test_kernel_gate_reads_the_tuned_blocks(tpp, fits):
    """The gate sizes the kernel as it will launch: tuned blocks with
    many tasks per program widen the strip and output blocks (FFT
    128->128 at R=8: ~25 MiB at one task, ~74 MiB at sixteen)."""
    fits_at = pipeline.TransformedAlgorithm._kernel_fits
    tr = transforms.FFTTransform(t=16, k=3)
    spec = ConvSpec(h=112, w=112, c_in=128, c_out=128, k=3, pad=1)
    blocks = BlockConfig(r=8, tasks_per_program=tpp)
    assert fits_at(tr, spec, 8, blocks) == fits
    assert fits_at(tr, spec, 8, None)


def test_kernel_vmem_budget_is_the_v5e_model_figure():
    """The planner's fast-memory figure for the v5e is what one kernel
    call may claim, not a separate guess."""
    from repro.kernels.fused_tile import kernel

    assert analysis.TPU_V5E.fast_shared_bytes == kernel.VMEM_BUDGET_BYTES
    assert analysis.TPU_V5E.tile_backend == "pallas"


def test_f64_gated_and_scan_fallback_exact(monkeypatch):
    """f64 is gated off the f32-basis kernel spec, and the dispatcher's
    scan fallback (the interpreting oracle) still serves exactly when
    the engine is forced off via the env override."""
    tr = transforms.WinogradTransform(m=3, k=3)
    assert not engine_supported(tr, jnp.dtype(jnp.float64))
    assert engine_supported(tr, jnp.dtype(jnp.float32))

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((1, 10, 10, 2)) * 0.1, jnp.float32)
    wk = jnp.asarray(rng.standard_normal((3, 3, 2, 2)) * 0.1, jnp.float32)
    monkeypatch.setenv("REPRO_TILE_BACKEND", "scan")
    y = pipeline.fused_tile_conv(x, wk, tr, pad=1)
    assert _rel(y, _lax_ref(x, wk, pad=1)) < 5e-5


# --------------------------------------------------- block-shape wisdom


def _fresh(path):
    """Simulate a process restart: the mtime-validated in-memory wisdom
    cache is dropped, forcing a re-read from disk."""
    tune._WISDOM_CACHE.clear()
    return path


def test_block_wisdom_roundtrip_survives_atomic_rewrite(tmp_path):
    """Tuned block shapes written by `tuned_blocks` survive tune.py's
    atomic stamped rewrites of *other* entries and a process restart --
    the plan -> tune -> replan cycle's persistence contract."""
    path = tmp_path / "wisdom.json"
    tr = transforms.WinogradTransform(m=3, k=3)
    tuned = tune.tuned_blocks(
        12, 12, 2, 3, transform=tr, wisdom_path=path, backend="xla"
    )
    assert isinstance(tuned, BlockConfig)

    # an unrelated tuner rewrites the file (atomic replace, gen bump)
    tune.tuned_blocks(
        12, 12, 3, 2, transform=transforms.WinogradTransform(m=4, k=3),
        wisdom_path=path, backend="xla",
    )

    looked = tune.lookup_blocks(
        12, 12, 2, 3, transform=tr, wisdom_path=_fresh(path)
    )
    assert looked == tuned
    # the stamped entry merged, not clobbered: generation is monotonic
    # and the serialized blocks carry the tuned shape
    raw = json.loads(path.read_text())
    key = [k for k in raw if ":winograd:12x12x2->3:" in k]
    assert len(key) == 1
    entry = raw[key[0]]
    assert entry["blocks"] == tuned.to_wisdom()
    assert entry["gen"] >= 1 and entry["ts"] > 0


def test_tuned_blocks_preserves_prior_r(tmp_path):
    """A previously tuned R on the same key survives block tuning: the
    two wisdom dimensions merge into one stamped entry."""
    path = tmp_path / "wisdom.json"
    tr = transforms.WinogradTransform(m=3, k=3)
    tune.tuned_r(12, 12, 2, 3, transform=tr, wisdom_path=path)
    r_before = tune.lookup_r(12, 12, 2, 3, transform=tr, wisdom_path=path)
    assert r_before is not None
    tune.tuned_blocks(
        12, 12, 2, 3, transform=tr, wisdom_path=path, backend="xla"
    )
    assert tune.lookup_r(
        12, 12, 2, 3, transform=tr, wisdom_path=_fresh(path)
    ) == r_before
    assert tune.lookup_blocks(
        12, 12, 2, 3, transform=tr, wisdom_path=path
    ) is not None

    # and the reverse: an R pass on a blocks-only key merges too
    tr2 = transforms.WinogradTransform(m=4, k=3)
    tuned = tune.tuned_blocks(
        12, 12, 2, 3, transform=tr2, wisdom_path=path, backend="xla"
    )
    tune.tuned_r(12, 12, 2, 3, transform=tr2, wisdom_path=path)
    assert tune.lookup_blocks(
        12, 12, 2, 3, transform=tr2, wisdom_path=_fresh(path)
    ) == tuned


def test_plan_consumes_tuned_blocks_and_run_accepts_them(tmp_path):
    """Planning resolves tuned blocks into `params["blocks"]` (so the
    auto ranking prices the tuned engine) and execution reconstructs the
    BlockConfig -- and stays exact."""
    path = tmp_path / "wisdom.json"
    tr = transforms.WinogradTransform(m=3, k=3)
    blocks = BlockConfig(r=2, tasks_per_program=2)
    key = tune._key(tr, 12, 12, 2, 3)
    path.write_text(json.dumps(
        {key: {"blocks": blocks.to_wisdom(), "gen": 1, "ts": 1.0}}
    ))

    spec = ConvSpec(h=12, w=12, c_in=2, c_out=3, k=3, pad=1)
    ap = registry.plan_conv(
        spec, BIG_HW, algo="l3_fused", hints={"m": 3},
        wisdom_path=_fresh(path),
    )
    assert ap.params["blocks"] == blocks.to_wisdom()
    assert BlockConfig.from_wisdom(ap.params["blocks"]) == blocks

    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((2, 12, 12, 2)) * 0.1, jnp.float32)
    wk = jnp.asarray(rng.standard_normal((3, 3, 2, 3)) * 0.1, jnp.float32)
    alg = registry.get(ap.algo)
    y = alg.execute(x, wk, alg.prepare_weights(wk, ap), ap)
    assert _rel(y, _lax_ref(x, wk, pad=1)) < 5e-5


def test_untuned_plan_keeps_legacy_cost_charge(tmp_path):
    """Without tuned blocks the auto cost falls back to the static
    stride^2 charge; with them it prices the tuned engine -- the two
    sides of `fused_auto_cost`."""
    spec = ConvSpec(h=12, w=12, c_in=2, c_out=3, k=3, pad=1, stride=2)
    ap_untuned = registry.plan_conv(
        spec, BIG_HW, algo="l3_fused", hints={"m": 3},
        wisdom_path=tmp_path / "empty.json",
    )
    assert "blocks" not in ap_untuned.params
    ta = transforms.WinogradTransform(m=3, k=3).algebra
    tuned_cost = analysis.engine_cost_ta(
        BIG_HW, spec.c_in, spec.c_out, ta, 4, stride=spec.stride
    )
    assert tuned_cost is not None and tuned_cost > 0
    assert ap_untuned.cost != pytest.approx(tuned_cost)


# ------------------------------------------------------ calibration


def test_calibration_measures_once_and_caches(tmp_path):
    path = tmp_path / "wisdom.json"
    assert tune.lookup_calibration(path) is None
    first = tune.measure_calibration(path)
    assert first["peak_flops"] > 0 and first["dram_bw"] > 0
    again = tune.measure_calibration(_fresh(path))
    assert again["ts"] == first["ts"]  # served from the stamped cache
    assert tune.lookup_calibration(path)["peak_flops"] == first["peak_flops"]


def test_calibrated_hw_rescales_roofs(tmp_path):
    path = tmp_path / "wisdom.json"
    tune.measure_calibration(path)
    hw = analysis.calibrated_hw(analysis.SKYLAKE_X, wisdom_path=path)
    assert hw.name.endswith(":calibrated")
    assert hw.peak_flops > 0 and hw.dram_bw > 0
    # the fast-shared roof preserves the base machine's compute-to-fast
    # ratio, so residency heuristics keep their meaning after rescaling
    base = analysis.SKYLAKE_X
    assert hw.peak_flops / hw.fast_shared_bw == pytest.approx(
        base.cmr_fast, rel=1e-6
    )
