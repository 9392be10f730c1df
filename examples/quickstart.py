"""Quickstart: the algorithm registry through the public API.

A convolution *problem* is a `ConvSpec`; each *realization* (direct,
three_stage, l3_fused, fft_fused, l3_fused_pallas) is a registered
`Algorithm` with a plan/prepare/execute lifecycle; `conv2d` is a thin
dispatcher that resolves ``algo="auto"`` through the registry's roofline
cost model and the wisdom file.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp
import numpy as np

from repro.core import ConvSpec, analysis as an, conv2d, conv2d_direct, registry

# a ResNet conv layer (64 channels, 56x56) -- the paper's sweet spot
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((2, 56, 56, 64)) * 0.1, jnp.float32)
w = jnp.asarray(rng.standard_normal((3, 3, 64, 64)) * 0.1, jnp.float32)

ref = conv2d_direct(x, w, pad=1)
# every algorithm whose domain covers this problem (the registry also
# holds e.g. the temporal conv1d algorithm, which declines 2-D specs)
for algo in registry.supporting(registry.ConvSpec.from_tensors(x, w, pad=1)):
    y = conv2d(x, w, pad=1, algo=algo)
    err = float(jnp.abs(y - ref).max() / jnp.abs(ref).max())
    print(f"{algo:16s} out={tuple(y.shape)} rel_err_vs_direct={err:.2e}")

# the same problem as data: what does the registry plan for it?
spec = ConvSpec.from_tensors(x, w, pad=1)
plan = registry.plan_conv(spec, an.SKYLAKE_X)
print(
    f"\nauto on SkylakeX -> {plan.algo} params={plan.params} "
    f"util~{plan.predicted_util:.2f}"
)

# new scenarios ride the same dispatcher: stride-2 downsampling layers
# reach the transformed paths via tile-decimation, grouped layers fall
# back to direct until a transformed algorithm registers grouped support
y2 = conv2d(x, w, pad=1, stride=2)
wg = jnp.asarray(rng.standard_normal((3, 3, 16, 64)) * 0.1, jnp.float32)
yg = conv2d(x, wg, pad=1, groups=4)
print(f"stride=2 out={tuple(y2.shape)}  groups=4 out={tuple(yg.shape)}")
spec_g = ConvSpec.from_tensors(x, wg, pad=1, groups=4)
print(f"groups=4 supported by: {registry.supporting(spec_g)}")

# the paper's "wisdom": when does fusion win? (S5 analytical model)
for c in (64, 128, 256, 512):
    choice = registry.plan_conv(
        ConvSpec(h=56, w=56, c_in=c, c_out=c, k=3, pad=1), an.SKYLAKE_X
    ).algo
    print(f"{c:4d} channels on SkylakeX -> {choice}")
print("TPU v5e CMR(HBM) =", round(an.TPU_V5E.cmr_dram), "(7x SkylakeX DRAM ->"
      " fusion matters more on TPU; see DESIGN.md S2)")

# whole nets go through the Engine: compile once (plan -> staged
# ExecProgram with cross-layer fusion groups), then serve.  Adjacent
# small-channel convs collapse into one resident stage -- the paper's
# L3-residency argument lifted to the net level.
from repro.configs.convnets import vgg_mixed_channel
from repro.convserve import Engine, init_weights

spec = vgg_mixed_channel(c_in=3)
net = Engine(hw=an.SKYLAKE_X).compile(
    spec, init_weights(spec, seed=0), input_hw=(64, 64)
)
print(f"\n{spec.name} staged program ({net.program.n_fused} fusion groups):")
print(net.describe())
y = net(jnp.zeros((1, 64, 64, 3), jnp.float32))
print(f"net out={tuple(y.shape)}  stats={net.stats()}")
