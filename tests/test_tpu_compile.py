"""Compile the served path's Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers with ``backend="pallas"`` and compiles
against a `v5e:2x2` topology the TPU compiler describes without a chip,
which refuses what the chip would refuse (block shapes off the (8, 128)
tiling, unsupported relayouts, VMEM over the limit).  The topology and
everything built from it live in module-scoped fixtures: only the test
worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.convnets import vgg_mixed_channel
from repro.convserve import Engine, init_weights
from repro.core import analysis, registry, transforms
from repro.kernels.fused_tile import BlockConfig, conv2d_fused_tile

WINO = transforms.WinogradTransform(m=5, k=3)
FFT = transforms.FFTTransform(t=16, k=3)
WAVE = 8  # the served wave

# (transform, side, c_in, c_out, groups): vgg-mixed's layers at 224 px
# in both families (the v5e plan takes FFT for 64/128 channels and
# Winograd for 256), fft-fewchannel's, and a resnext-grouped layer
CASES = {
    "wino-3-64-224": (WINO, 224, 3, 64, 1),
    "wino-64-64-224": (WINO, 224, 64, 64, 1),
    "wino-64-128-112": (WINO, 112, 64, 128, 1),
    "wino-128-128-112": (WINO, 112, 128, 128, 1),
    "wino-128-256-56": (WINO, 56, 128, 256, 1),
    "wino-256-256-56": (WINO, 56, 256, 256, 1),
    "fft-3-64-224": (FFT, 224, 3, 64, 1),
    "fft-64-64-224": (FFT, 224, 64, 64, 1),
    "fft-64-128-112": (FFT, 112, 64, 128, 1),
    "fft-128-128-112": (FFT, 112, 128, 128, 1),
    "fft-4-8-64": (FFT, 64, 4, 8, 1),
    "fft-8-8-64": (FFT, 64, 8, 8, 1),
    "wino-32-32-g4-64": (WINO, 64, 32, 32, 4),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",))


@pytest.fixture
def no_cache():
    """Compiles for a described chip cannot be read back here: keep
    them out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_tile_kernel_compiles_for_v5e(case, one_chip, no_cache):
    tr, side, c_in, c_out, groups = CASES[case]

    def conv(x, w):
        return conv2d_fused_tile(
            x, w, tr, pad=1, groups=groups, backend="pallas",
            blocks=BlockConfig(r=8, tasks_per_program=1),
        )

    x = jax.ShapeDtypeStruct((WAVE, side, side, c_in), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, c_in // groups, c_out), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(conv).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", ["wino-64-128-112", "fft-64-128-112"])
def test_tile_kernel_is_named_in_the_compiled_program(case, one_chip,
                                                      no_cache):
    """The profiler shows a device op by its HLO instruction's name: the
    tile kernel's is `convserve_tile_<family>_t<T>`."""
    tr, side, c_in, c_out, groups = CASES[case]

    def conv(x, w):
        return conv2d_fused_tile(x, w, tr, pad=1, backend="pallas",
                                 blocks=BlockConfig(r=8))

    x = jax.ShapeDtypeStruct((WAVE, side, side, c_in), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, c_in, c_out), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(conv).lower(x, w).compile().as_text()
    name = f"convserve_tile_{tr.family}_t{tr.kernel_spec().t}"
    assert f"%{name}" in text and "tpu_custom_call" in text


def test_sharded_wave_compiles_per_device_on_four_chips(
    data_mesh, monkeypatch, no_cache
):
    """The 4-device wave program runs the kernel on every device's own
    rows: the compiled text holds one kernel per transformed layer and
    no all-gather."""
    monkeypatch.setenv("REPRO_TILE_BACKEND", "pallas")
    spec = vgg_mixed_channel(c_in=3)
    side = 64
    net = Engine(hw=analysis.TPU_V5E).compile(
        spec, init_weights(spec, seed=0), input_hw=(side, side)
    )
    tiled = [
        p for p in net.plan.layers if registry.get(p.algo).chain_family
    ]
    assert tiled, "the v5e plan should transform some layers"
    ex = net.executor
    x_shape = (WAVE, side, side, 3)
    fn = ex._program(
        np.empty(x_shape), np.empty((WAVE, 2)), data_mesh
    )

    def shapes(tree, spec_):
        sharding = NamedSharding(data_mesh, spec_)
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            tree,
        )

    rows = NamedSharding(data_mesh, P("data"))
    compiled = fn.lower(
        jax.ShapeDtypeStruct(x_shape, jnp.float32, sharding=rows),
        shapes(ex.weights, P()),
        shapes(ex._fetch_transforms(), P()),
        jax.ShapeDtypeStruct((WAVE, 2), jnp.int32, sharding=rows),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(tiled)
    assert "all-gather" not in text


def test_planned_wave_names_its_tile_kernels_in_the_lowered_text(
    one_chip, monkeypatch, no_cache
):
    """What `chip_smoke.py` checks: the lowered one-chip wave program
    names a `convserve_tile_<family>_t<T>` kernel for every transform
    family the v5e plan uses."""
    monkeypatch.setenv("REPRO_TILE_BACKEND", "pallas")
    spec = vgg_mixed_channel(c_in=3)
    side = 64
    net = Engine(hw=analysis.TPU_V5E).compile(
        spec, init_weights(spec, seed=0), input_hw=(side, side)
    )
    families = {
        registry.get(p.algo).tile_algebra(p.algo_plan()).family
        for p in net.plan.layers if registry.get(p.algo).chain_family
    }
    assert families, "the v5e plan should transform some layers"
    ex = net.executor
    x_shape = (WAVE, side, side, 3)
    fn = ex._program(np.empty(x_shape), np.empty((WAVE, 2)), None)

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            tree,
        )

    text = fn.lower(
        jax.ShapeDtypeStruct(x_shape, jnp.float32, sharding=one_chip),
        shapes(ex.weights),
        shapes(ex._fetch_transforms()),
        jax.ShapeDtypeStruct((WAVE, 2), jnp.int32, sharding=one_chip),
    ).as_text()
    named = set(re.findall(r"convserve_tile_([a-z0-9]+)_t[0-9]+", text))
    assert named == families
