"""Elastic fleet serving under a simulated clock: sharded waves match
the single-replica oracle to float32 rounding, replicas add simulated
parallelism, the autoscaler grows/shrinks with hysteresis + admission
control, crashed replicas orphan waves into bounded-retry re-dispatch,
probes catch slow replicas and repair shared-cache corruption, and the
accounting invariant (admitted == served + lost) survives every drill."""

import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.convnets import tiny_testnet
from repro.convserve import Engine, init_weights
from repro.convserve.fleet import (
    Autoscaler,
    AutoscalerConfig,
    ElasticPool,
    FixedServiceModel,
    FleetRuntime,
    LOSS_NO_HEALTHY_REPLICA,
    LOSS_REASONS,
    LOSS_RETRIES_EXHAUSTED,
    REPLICATE,
    SHARD,
    ShardedWaveExecutor,
    plan_weight_placement,
    shard_bounds,
)
from repro.convserve.runtime import (
    REJECT_SCALING,
    RuntimeConfig,
    SimClock,
    diurnal_rate,
    diurnal_trace,
    make_images,
    merge_traces,
    poisson_trace,
)
from repro.core import analysis
from repro.runtime.fault import (
    FAULT_CACHE_CORRUPT,
    FAULT_CRASH,
    FAULT_SLOW,
    FaultPlan,
    ReplicaFault,
)

BIG_HW = analysis.HardwareModel(
    name="big", peak_flops=1e12, dram_bw=1e11, fast_shared_bw=5e11,
    fast_shared_bytes=1 << 30, private_bytes=1 << 24,
)

SPEC = tiny_testnet(4)

SERVICE = FixedServiceModel(base_s=0.004, per_image_s=0.002)


def _fleet(n=2, *, shards=1, clock=None, cfg=None, autoscaler=None,
           adapt=None, fault_plan=None, **pool_kwargs):
    """Deterministic fleet: SimClock + fixed service model."""
    ws = init_weights(SPEC, seed=5)
    engine = Engine(hw=BIG_HW)
    clock = clock or SimClock()
    pool = ElasticPool.build(
        engine, SPEC, ws, n=n, clock=clock, input_hw=(16, 16),
        shards=shards, service_model=SERVICE, fault_plan=fault_plan,
        **pool_kwargs,
    )
    cfg = cfg or RuntimeConfig(
        buckets=(16,), max_batch=4, queue_depth=256,
        slo_s=0.25, service_est_s=0.012,
    )
    rt = FleetRuntime(pool, cfg, clock=clock,
                      autoscaler=autoscaler, adapt=adapt)
    return rt, clock


def _assert_rows_match(got, want):
    """Sharded rows run at a smaller batch than the unsharded wave, and
    XLA may block a GEMM or convolution differently per batch size,
    reordering float32 sums: equal to rounding (~1e-8 seen on outputs of
    scale ~1e-1), not bitwise.  Bound: 1e-5 of the output's scale."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= 1e-5 * scale


def _accounting(rt) -> dict:
    c = rt.stats()["counters"]
    served = c.get("images", 0)
    lost = c.get("lost_images", 0)
    assert served + lost == c.get("admitted", 0)
    return {"served": served, "lost": lost,
            "admitted": c.get("admitted", 0),
            "rejected": c.get("rejected", 0)}


class _AdaptStub:
    """Records pause/resume bracketing (the replanner's fleet surface)."""

    def __init__(self):
        self.events = []

    def pause(self, reason="x"):
        self.events.append(("pause", reason))

    def resume(self):
        self.events.append(("resume", None))


# ------------------------------------------------------------ traces


def test_diurnal_trace_is_seeded_and_shaped():
    a = diurnal_trace(50.0, 500, seed=3, period_s=10.0, sizes=(12, 16))
    b = diurnal_trace(50.0, 500, seed=3, period_s=10.0, sizes=(12, 16))
    assert a == b
    assert [r.t for r in a] == sorted(r.t for r in a)
    # the trough sits at t=0, the peak half a period in: 500 arrivals
    # at a 50 Hz mean span one full 10 s period, so the early-morning
    # window must be far quieter than the midday one
    trough = sum(1 for r in a if r.t % 10.0 < 1.5)
    peak = sum(1 for r in a if 4.0 <= r.t % 10.0 < 6.0)
    assert peak > 2 * trough > 0
    with pytest.raises(ValueError):
        diurnal_rate(50.0, depth=1.5)


def test_diurnal_rate_profile():
    rate = diurnal_rate(100.0, depth=0.5, period_s=10.0)
    assert rate(0.0) == pytest.approx(50.0)  # trough
    assert rate(5.0) == pytest.approx(150.0)  # peak
    assert rate(10.0) == pytest.approx(50.0)  # periodic


def test_merge_traces_dense_rids_preserve_payload():
    a = poisson_trace(100.0, 20, seed=1, sizes=(12,), priorities=(0,))
    b = poisson_trace(80.0, 15, seed=2, sizes=(16,), priorities=(2,))
    m = merge_traces(a, b)
    assert len(m) == 35
    assert [r.rid for r in m] == list(range(35))
    assert [r.t for r in m] == sorted(r.t for r in m)
    # payloads ride through: priority/size distributions are preserved
    assert sum(1 for r in m if r.priority == 2) == 15
    assert sum(1 for r in m if r.h == 12) == 20
    assert make_images(m, 4, seed=1).keys() == set(range(35))


# ----------------------------------------------------------- sharding


def test_shard_bounds_partition():
    assert shard_bounds(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert shard_bounds(2, 4) == [(0, 1), (1, 2)]  # never empty shards
    assert shard_bounds(8, 1) == [(0, 8)]
    assert shard_bounds(0, 4) == []
    # contiguous + exhaustive
    bounds = shard_bounds(17, 5)
    assert bounds[0][0] == 0 and bounds[-1][1] == 17
    assert all(bounds[i][1] == bounds[i + 1][0] for i in range(4))


def test_sharded_executor_bit_exact_on_ragged_wave():
    ws = init_weights(SPEC, seed=5)
    engine = Engine(hw=BIG_HW)
    net = engine.compile(SPEC, ws, input_hw=(16, 16))
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((5, 16, 16, 4)) * 0.1).astype(np.float32)
    ext = np.array(
        [[16, 16], [12, 12], [16, 14], [8, 16], [0, 0]], np.int32
    )
    y1 = np.asarray(net(x, ext))
    sharded = ShardedWaveExecutor(
        engine.compile(SPEC, ws, plan=net.plan, input_hw=(16, 16)),
        shards=3,
    )
    _assert_rows_match(sharded(x, ext), y1)
    # passthroughs keep the CompiledNet duck type intact
    assert sharded.spec is net.spec and sharded.cache is net.cache


def test_weight_placement_is_a_threshold_decision():
    ws = init_weights(SPEC, seed=5)
    engine = Engine(hw=BIG_HW)
    net = engine.compile(SPEC, ws, input_hw=(16, 16))
    net(np.zeros((1, 16, 16, 4), np.float32))  # make transforms resident
    tiny = plan_weight_placement(net, threshold_bytes=1)
    huge = plan_weight_placement(net, threshold_bytes=1 << 40)
    consuming = [
        layer for layer, d in tiny.items() if d["bytes"] > 0
    ]
    assert consuming, "tiny_testnet should have transformed layers"
    assert all(tiny[k]["placement"] == SHARD for k in consuming)
    assert all(d["placement"] == REPLICATE for d in huge.values())


# -------------------------------------------- exactness vs the oracle


def test_fleet_matches_single_replica_oracle_with_ragged_waves():
    trace = poisson_trace(
        45.0, 40, seed=7, sizes=(8, 12, 16), deadline_s=0.08,
    )
    images = make_images(trace, 4, seed=1)

    def serve(n, shards):
        rt, _ = _fleet(n, shards=shards, cfg=RuntimeConfig(
            buckets=(16,), max_batch=4, queue_depth=128,
            slo_s=0.1, service_est_s=0.01,
        ))
        rt.warmup([2, 4])
        out = rt.play(trace, images)
        return out, rt.stats()

    fleet_out, doc = serve(3, shards=2)
    oracle_out, _ = serve(1, shards=1)
    assert fleet_out.keys() == oracle_out.keys() == {a.rid for a in trace}
    for rid in oracle_out:
        _assert_rows_match(fleet_out[rid], oracle_out[rid])
    # the deadline-flushed waves make the exactness claim cover ragged
    # partial batches, not just full ones
    assert doc["scheduler"]["partial_waves"] >= 1


# ------------------------------------------------- simulated elasticity


def test_replicas_add_simulated_parallelism():
    def makespan(n):
        trace = poisson_trace(5000.0, 240, seed=3, sizes=(16,))
        rt, clock = _fleet(n, cfg=RuntimeConfig(
            buckets=(16,), max_batch=4, queue_depth=512,
            slo_s=None, service_est_s=0.012,
        ))
        rt.warmup()
        rt.play(trace, make_images(trace, 4, seed=1))
        assert _accounting(rt)["served"] == 240
        return clock.now()

    m1, m4 = makespan(1), makespan(4)
    assert m4 < m1 / 2.5, (m1, m4)


def test_autoscaler_grows_under_pressure_and_gates_admission():
    adapt = _AdaptStub()
    auto = AutoscalerConfig(
        min_replicas=1, max_replicas=4,
        tick_interval_s=0.01, cooldown_s=0.05,
        queue_high=2.0, queue_low=0.1,
        slack_comfort_s=math.inf,  # never scale back down in this test
        admission_queue_per_replica=12.0,
    )
    rt, clock = _fleet(1, autoscaler=auto, adapt=adapt, startup_s=0.5)
    rt.warmup()
    img = np.zeros((16, 16, 4), np.float32)
    # flood one instant: queue pressure >> queue_high
    for i in range(40):
        rt.submit(img, rid=i, deadline_s=10.0)
    rt.run_until(0.2)  # several ticks: scale-up starts, replicas warm
    counts = rt.pool.counts()
    assert counts.get("starting", 0) >= 1, counts
    assert rt.autoscaler.scaling(clock.now())
    assert ("pause", "scale_event:up") in adapt.events
    # during the scale-up, admission above the READY replicas' cap is
    # shed with the reason-coded ``scaling`` rejection
    rejected = []
    for i in range(40, 80):
        r = rt.submit(img, rid=i, deadline_s=10.0)
        if r is not None:
            rejected.append(r)
    assert rejected and all(
        r.reason == REJECT_SCALING for r in rejected
    )
    # after startup the newcomers serve; the drain completes everything
    rt.run_until(1.0)
    assert rt.pool.ready_count() >= 2
    rt.drain()
    acct = _accounting(rt)
    assert acct["served"] == acct["admitted"] > 0
    assert acct["rejected"] == len(rejected)
    assert ("resume", None) in adapt.events  # settled after the reshape


def test_autoscaler_scales_down_and_drains_before_retire():
    auto = AutoscalerConfig(
        min_replicas=1, max_replicas=4,
        tick_interval_s=0.02, cooldown_s=0.05,
        queue_high=50.0, queue_low=0.5, slack_comfort_s=-math.inf,
    )
    rt, clock = _fleet(3, autoscaler=auto)
    rt.warmup()
    img = np.zeros((16, 16, 4), np.float32)
    for i in range(12):
        rt.submit(img, rid=i, deadline_s=5.0)
    rt.run_until(2.0)  # queue drains, then idle ticks shrink the fleet
    rt.drain()
    counts = rt.pool.counts()
    assert counts.get("retired", 0) >= 1, counts
    assert counts.get("ready", 0) >= auto.min_replicas
    acct = _accounting(rt)
    assert acct["served"] == 12 and acct["lost"] == 0


def test_pool_retire_waits_for_inflight_wave():
    rt, clock = _fleet(2)
    rt.warmup()
    img = np.zeros((16, 16, 4), np.float32)
    for i in range(8):  # two full waves: both replicas busy
        rt.submit(img, rid=i, deadline_s=5.0)
    rt.poll()
    assert rt.pool.ready_count() == 2 and not rt.pool.has_capacity()
    gone = rt.pool.retire(1)
    assert gone and rt.pool.counts().get("draining") == 1
    rt.drain()
    # the draining replica finished its wave before retiring: nothing
    # was lost and the wave landed
    assert rt.pool.counts().get("retired") == 1
    assert _accounting(rt)["served"] == 8


# ------------------------------------------------------------- faults


def test_crash_orphans_wave_into_retry_without_double_count():
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.016, kind=FAULT_CRASH, replica=0)], clock=clock
    )
    rt, _ = _fleet(2, clock=clock, fault_plan=fp)
    rt.warmup()
    trace = poisson_trace(400.0, 48, seed=3, sizes=(16,), deadline_s=1.0)
    rt.play(trace, make_images(trace, 4, seed=1))
    p = rt.stats()["pool"]
    assert p["failures"] == 1 and p["orphaned"] >= 1 and p["retries"] >= 1
    acct = _accounting(rt)
    assert acct["served"] == 48 and acct["lost"] == 0
    # a re-dispatched wave is still ONE wave everywhere it is counted
    doc = rt.stats()
    assert doc["counters"]["waves"] == doc["scheduler"]["waves"]
    assert doc["counters"]["images"] == 48  # no request served twice
    assert len(rt.results) == 48


def test_retries_exhausted_is_a_reason_coded_loss():
    clock = SimClock()
    fp = FaultPlan([
        ReplicaFault(t=0.010, kind=FAULT_CRASH, replica=0),
        ReplicaFault(t=0.012, kind=FAULT_CRASH, replica=1),
    ], clock=clock)
    rt, _ = _fleet(2, clock=clock, fault_plan=fp, max_retries=0)
    rt.warmup()
    img = np.zeros((16, 16, 4), np.float32)
    for i in range(16):
        rt.submit(img, rid=i, deadline_s=1.0)
    rt.drain()
    acct = _accounting(rt)  # asserts served + lost == admitted
    assert acct["lost"] >= 1
    assert set(rt.losses.values()) <= set(LOSS_REASONS)
    assert LOSS_RETRIES_EXHAUSTED in set(rt.losses.values())
    # queued waves dispatched after total fleet loss are losses too,
    # with their own reason
    p = rt.stats()["pool"]
    assert p["states"].get("failed") == 2
    if LOSS_NO_HEALTHY_REPLICA in p["losses"]:
        assert p["losses"][LOSS_NO_HEALTHY_REPLICA] >= 1
    # every admitted rid is in results or losses -- none vanished
    with rt._lock:
        assert set(rt.results) | set(rt.losses) == set(range(16))


def test_autoscaler_replaces_failed_replicas_ignoring_cooldown():
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.05, kind=FAULT_CRASH, replica=0)], clock=clock
    )
    auto = AutoscalerConfig(
        min_replicas=2, max_replicas=4,
        tick_interval_s=0.02, cooldown_s=1e9,  # cooldown would block "up"
        queue_high=1e9, queue_low=0.0,
    )
    rt, _ = _fleet(2, clock=clock, fault_plan=fp, autoscaler=auto,
                   startup_s=0.05)
    rt.warmup()
    rt.run_until(0.5)
    assert rt.stats()["autoscaler"]["replacements"] >= 1
    assert rt.pool.ready_count() >= 2


def test_cache_corruption_detected_and_repaired_by_probes():
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.5, kind=FAULT_CACHE_CORRUPT)], clock=clock
    )
    rt, _ = _fleet(2, clock=clock, fault_plan=fp, probe_interval_s=0.3)
    rt.warmup()
    rt.run_until(2.0)
    p = rt.stats()["pool"]
    assert p["probe_mismatches"] >= 2  # every replica saw the bad bytes
    assert p["cache_repairs"] == 1
    assert p["quarantines"] == 0  # shared fault, not a replica fault
    # post-repair serving is exact again
    trace = poisson_trace(200.0, 12, seed=3, sizes=(16,))
    out = rt.play(trace, make_images(trace, 4, seed=1))
    assert len(out) == 12


def test_slow_replica_is_quarantined_by_probes():
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.1, kind=FAULT_SLOW, replica=1, factor=8.0)],
        clock=clock,
    )
    rt, _ = _fleet(2, clock=clock, fault_plan=fp, probe_interval_s=0.2,
                   slow_quarantine_factor=2.5)
    rt.warmup()
    rt.run_until(1.0)
    p = rt.stats()["pool"]
    assert p["quarantines"] == 1
    assert p["states"].get("quarantined") == 1
    # the healthy replica keeps serving
    trace = poisson_trace(200.0, 12, seed=3, sizes=(16,))
    rt.play(trace, make_images(trace, 4, seed=1))
    assert _accounting(rt)["served"] == 12


def test_no_healthy_replica_losses_resolve_immediately():
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.001, kind=FAULT_CRASH, replica=0)], clock=clock
    )
    rt, _ = _fleet(1, clock=clock, fault_plan=fp)
    rt.warmup()
    clock.advance(0.01)
    rt.pool.advance(clock.now())
    img = np.zeros((16, 16, 4), np.float32)
    for i in range(4):
        rt.submit(img, rid=i, deadline_s=0.05)
    rt.drain()  # must terminate: doomed waves resolve to losses
    acct = _accounting(rt)
    assert acct["served"] == 0 and acct["lost"] == 4
    assert set(rt.losses.values()) == {LOSS_NO_HEALTHY_REPLICA}


# ---------------------------------------------------------- telemetry


def test_telemetry_schema_is_stable_across_scale_events():
    auto = AutoscalerConfig(
        min_replicas=1, max_replicas=3,
        tick_interval_s=0.01, cooldown_s=0.05,
        queue_high=2.0, queue_low=0.1,
    )
    clock = SimClock()
    fp = FaultPlan(
        [ReplicaFault(t=0.08, kind=FAULT_CRASH, replica=0)], clock=clock
    )
    rt, _ = _fleet(1, clock=clock, autoscaler=auto, fault_plan=fp,
                   startup_s=0.1)
    rt.warmup()
    img = np.zeros((16, 16, 4), np.float32)

    def schema(doc):
        top = set(doc)
        hist = {k: set(v) for k, v in doc["latency"].items()}
        return top, hist

    for i in range(30):
        rt.submit(img, rid=i, deadline_s=5.0)
    rt.run_until(0.05)
    top0, hist0 = schema(rt.stats())
    rt.run_until(0.2)  # crash + replacement + scale-up mid-trace
    top1, hist1 = schema(rt.stats())
    rt.drain()
    top2, hist2 = schema(rt.stats())
    assert top0 == top1 == top2
    for h in (hist0, hist1, hist2):
        for keys in h.values():
            assert keys == {"count", "mean_s", "p50_s", "p95_s",
                            "p99_s", "max_s"}
    # mid-scale histograms only ever grow (no counter reset mid-trace)
    doc = rt.stats()
    assert doc["counters"]["waves"] == doc["scheduler"]["waves"]
    acct = _accounting(rt)
    assert acct["served"] + acct["lost"] == 30


def test_fleet_stats_sections_are_json_clean():
    import json as _json

    auto = AutoscalerConfig(min_replicas=1, max_replicas=2,
                            tick_interval_s=0.01)
    rt, _ = _fleet(1, autoscaler=auto)
    rt.warmup()
    trace = poisson_trace(200.0, 8, seed=3, sizes=(16,))
    rt.play(trace, make_images(trace, 4, seed=1))
    doc = rt.stats()
    _json.dumps(doc)  # autoscaler/pool/faults sections all serialize
    assert {"pool", "scheduler", "cache", "autoscaler"} <= set(doc)
    assert doc["autoscaler"]["ticks"] >= 1
    assert doc["pool"]["states"] == {"ready": 1}


# ------------------------------------------------- real-mesh execution


_ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_sharded_wave_on_forced_8_device_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import numpy as np
            from repro.configs.convnets import tiny_testnet
            from repro.convserve import Engine, init_weights
            from repro.convserve.fleet import (
                ShardedWaveExecutor, apply_placement, plan_weight_placement,
            )
            from repro.core import analysis
            from repro.launch.mesh import make_host_mesh

            hw = analysis.HardwareModel(
                name="big", peak_flops=1e12, dram_bw=1e11,
                fast_shared_bw=5e11, fast_shared_bytes=1 << 30,
                private_bytes=1 << 24,
            )
            mesh = make_host_mesh(model=1)  # data axis = 8
            spec = tiny_testnet(4)
            ws = init_weights(spec, seed=5)
            engine = Engine(hw=hw)
            net = engine.compile(spec, ws, input_hw=(16, 16))
            rng = np.random.default_rng(0)
            x = (rng.standard_normal((8, 16, 16, 4)) * 0.1).astype(
                np.float32)
            ext = np.array([[16, 16]] * 6 + [[12, 12], [8, 16]], np.int32)
            y_ref = np.asarray(net(x, ext))
            sh = ShardedWaveExecutor(
                engine.compile(spec, ws, plan=net.plan, input_hw=(16, 16)),
                shards=8, mesh=mesh,
            )
            y = np.asarray(sh(x, ext))
            err = np.abs(y - y_ref).max()
            assert err < 1e-5, err
            # weight placement executes on the real mesh
            placement = plan_weight_placement(net, mesh=mesh,
                                              threshold_bytes=1)
            counts = apply_placement(net, mesh, placement)
            assert counts["sharded"] + counts["replicated"] >= 1, counts
            y2 = np.asarray(sh(x, ext))
            assert np.abs(y2 - y_ref).max() < 1e-5
            print("MESH_OK", dict(mesh.shape), counts)
        """)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout


# ----------------------------------------------------- unit: autoscaler


def test_autoscaler_config_validates():
    with pytest.raises(ValueError):
        AutoscalerConfig(min_replicas=0)
    with pytest.raises(ValueError):
        AutoscalerConfig(queue_high=1.0, queue_low=2.0)
    with pytest.raises(ValueError):
        AutoscalerConfig(min_replicas=4, max_replicas=2)


def test_autoscaler_hysteresis_and_cooldown():
    rt, clock = _fleet(1, startup_s=0.01)
    cfg = AutoscalerConfig(
        min_replicas=1, max_replicas=3, tick_interval_s=0.1,
        cooldown_s=10.0, queue_high=4.0, queue_low=0.5,
    )
    depth = {"v": 0}
    auto = Autoscaler(rt.pool, cfg, queue_depth_fn=lambda: depth["v"])
    depth["v"] = 100
    clock.advance(0.15)
    assert auto.tick(clock.now()) == "up"
    rt.pool.advance(clock.now() + 0.02)
    # pressure persists but cooldown blocks the second grow
    clock.advance(0.15)
    assert auto.tick(clock.now()) is None
    # between ticks, nothing happens at all
    assert auto.tick(clock.now()) is None
    s = auto.stats()
    assert s["scale_ups"] == 1 and s["events"][0]["action"] == "up"
