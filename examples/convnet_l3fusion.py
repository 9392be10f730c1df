"""End-to-end ConvNet inference through the convserve Engine (the paper's
native use case): a mixed-channel VGG-style net is roofline-planned per
layer, adjacent small-channel convs are collapsed into cross-layer fusion
groups, kernels are pre-transformed into the cache, and requests are
served in shape-bucketed batched waves.

    PYTHONPATH=src python examples/convnet_l3fusion.py
"""

import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.convnets import vgg_mixed_channel
from repro.convserve import (
    ConvServeConfig,
    ConvServer,
    Engine,
    ImageRequest,
    init_weights,
    run_direct,
)


def main():
    enable_compile_cache()
    spec = vgg_mixed_channel(c_in=3)
    engine = Engine()  # TPU model on TPU backends, SkylakeX otherwise
    ws = init_weights(spec, seed=0)
    net = engine.compile(spec, ws, input_hw=(64, 64))

    print(f"net {spec.name!r} compiled for {engine.hw.name}:")
    for p in net.plan.layers:
        s = p.spec
        stride = f"/{s.stride}" if s.stride > 1 else "  "
        print(
            f"  layer {p.layer:2d}  {s.c_in:4d}->{s.c_out:<4d}{stride} "
            f"{p.algo:12s} params={p.params} util~{p.predicted_util:.2f}"
        )
    print("staged execution program (fusion groups keep the intermediate")
    print("activation resident instead of round-tripping DRAM):")
    print("  " + net.describe().replace("\n", "\n  "))
    algos = set(net.plan.algos())
    print(f"distinct algorithms in plan: {sorted(algos)}")
    assert len(algos) >= 2, "expected a mixed-algorithm plan"
    assert net.program.n_fused >= 1, "expected >=1 cross-layer fusion group"

    srv = ConvServer(net, ConvServeConfig(max_batch=4, buckets=(32, 64)))

    rng = np.random.default_rng(0)
    imgs = [
        rng.standard_normal((s, s, 3)).astype(np.float32) * 0.1
        for s in (64, 64, 32, 64, 32)
    ]
    reqs = [ImageRequest(i, im) for i, im in enumerate(imgs)]

    t0 = time.perf_counter()
    out = srv.run(reqs)
    print(
        f"wave 1: {len(out)} requests in {time.perf_counter() - t0:.2f}s "
        f"(compiles + kernel transforms) {srv.stats()}"
    )

    # numerical agreement with the all-direct oracle
    ref = np.asarray(run_direct(spec, ws, jnp.asarray(imgs[0])[None])[0])
    rel = float(np.abs(out[0] - ref).max() / np.abs(ref).max())
    print(f"fused-engine vs direct rel err {rel:.2e}")
    assert rel < 1e-3

    # same shapes again: transforms hit the cache, programs are reused
    t0 = time.perf_counter()
    srv.run([ImageRequest(10 + i, im) for i, im in enumerate(imgs)])
    warm = time.perf_counter() - t0
    stats = srv.stats()
    print(f"wave 2: warm {warm*1e3:.1f} ms  {stats}")
    assert stats["cache"]["hits"] > 0, "second wave should hit the cache"

    # throughput: fused program vs unfused vs all-direct on the big bucket
    x = jnp.asarray(
        rng.standard_normal((4, 64, 64, 3)) * 0.1, jnp.float32
    )
    unfused = engine.compile(spec, ws, input_hw=(64, 64), fuse=False)
    vendor = jax.jit(lambda x: run_direct(spec, ws, x))
    for fn in (vendor, net, unfused):
        jax.block_until_ready(fn(x))
    for name, fn in (
        ("fused engine", net),
        ("unfused engine", unfused),
        ("vendor(XLA)", vendor),
    ):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            ts.append(time.perf_counter() - t0)
        print(f"{name:15s} {sorted(ts)[len(ts) // 2] * 1e3 / 4:8.1f} ms/img")

    # per-stage wall times: where does the net actually spend its time?
    print("per-stage profile (separately jitted):")
    for label, secs in net.profile_stages(x):
        print(f"  {label:12s} {secs * 1e3:7.2f} ms")

    # the registry makes new scenarios one compile away: a stride-2
    # ResNet-style downsampling net plans transformed paths too (tile
    # decimation), its stride-1 head still fusing into a group
    from repro.configs.convnets import resnet_downsample

    rspec = resnet_downsample(c_in=3)
    rws = init_weights(rspec, seed=1)
    rnet = engine.compile(rspec, rws, input_hw=(64, 64))
    print(f"\nnet {rspec.name!r}:")
    print("  " + rnet.describe().replace("\n", "\n  "))
    xr = jnp.asarray(rng.standard_normal((2, 64, 64, 3)) * 0.1, jnp.float32)
    rref = run_direct(rspec, rws, xr)
    rel = float(jnp.abs(rnet(xr) - rref).max() / jnp.abs(rref).max())
    print(f"stride-2 net fused-engine vs direct rel err {rel:.2e}")
    assert rel < 1e-3


if __name__ == "__main__":
    main()
