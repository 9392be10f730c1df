"""Sharded wave execution over a `jax` mesh.

A wave is a batch of like-bucketed images; its rows are independent, so
the fleet splits them across the mesh's data axis and reassembles the
outputs in request order -- including ragged waves, whose per-sample
extent rows travel with their image rows, so the executor's masking
keeps every shard exact.

What holds is equality to float32 rounding, not bitwise equality: a
shard runs the same math as the unsharded wave, but at a smaller batch,
and XLA may block a GEMM or convolution differently at another batch
size, which reorders float32 sums (differences of ~1e-8 on the CPU).
The tests compare with a tolerance relative to the output's scale.

Two execution paths, picked per wave:

  * **mesh path** -- when the mesh really has >1 device on its data axis
    and the batch divides it, the batch (and extents) are `device_put`
    row-sharded over that axis and the replica's wave program runs once
    per device under `shard_map`: every device computes its own rows,
    Pallas kernels included, with weights and transforms replicated and
    no collective on the activations.
  * **logical path** -- otherwise the rows are split into `shards`
    contiguous groups run back to back through the same program.  On
    one device this buys nothing in wall time, but the fleet's
    discrete-event simulation charges a sharded wave `~service/shards`
    of *simulated* time, which is what the scale-out curve measures.

Weight-cache **replication vs. sharding** is a planner decision, not a
default (`plan_weight_placement`): a small pre-transformed kernel is
cheapest replicated on every device; a large transformed kernel stack
(the paper's 4 C C' T^2 matrices at high channel counts) is sharded
over the mesh so the fleet's resident-transform footprint stays flat as
devices grow.  `apply_placement` carries the decision out with
`jax.device_put` on the resident cache entries (value-identical moves,
enforced by `KernelCache.place`).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import registry

REPLICATE = "replicate"
SHARD = "shard"

# below this, a transformed kernel stack is cheaper replicated than the
# all-gather it would cost sharded (the mesh analogue of the planner's
# shared-level residency gate)
DEFAULT_SHARD_THRESHOLD_BYTES = 1 << 20


def shard_bounds(n: int, shards: int) -> List[Tuple[int, int]]:
    """Contiguous, balanced row ranges: `n` rows into at most `shards`
    non-empty ``(lo, hi)`` slices, earlier shards taking the remainder
    (the same split a data axis of size `shards` would produce)."""
    if n <= 0 or shards <= 0:
        return []
    shards = min(shards, n)
    base, rem = divmod(n, shards)
    bounds = []
    lo = 0
    for i in range(shards):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _data_axis_size(mesh) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("data", 1))


def plan_weight_placement(
    net,
    *,
    mesh=None,
    threshold_bytes: int = DEFAULT_SHARD_THRESHOLD_BYTES,
) -> Dict[int, dict]:
    """Per-conv-layer placement decision: ``{layer: {placement, bytes,
    why}}``.

    Prefers the ACTUAL resident transform bytes (post-warmup cache
    entries); falls back to the closed-form t^2 C C' estimate per
    transform family when a layer has not been prepared yet.  Layers
    whose algorithm consumes no pre-transform (direct, Pallas) have
    nothing to place and replicate trivially."""
    resident = {k[1]: k for k in net.cache_keys()}
    out: Dict[int, dict] = {}
    for p in net.plan.layers:
        alg = registry.get(p.algo)
        if not alg.consumes_wt:
            out[p.layer] = {
                "placement": REPLICATE, "bytes": 0,
                "why": "no pre-transformed kernels",
            }
            continue
        key = resident.get(p.layer)
        nb = net.cache.entry_nbytes(key) if key is not None else None
        why = "resident transform bytes"
        if nb is None:
            s = p.spec
            t = p.params.get("t") or (p.params.get("r", 2) + s.k - 1)
            elem = 8 if getattr(alg, "chain_family", "") == "fft" else 4
            nb = t * t * s.c_in * s.c_out * elem // max(s.groups, 1)
            why = "estimated (not yet prepared)"
        out[p.layer] = {
            "placement": SHARD if nb >= threshold_bytes else REPLICATE,
            "bytes": int(nb),
            "why": why,
        }
    return out


def apply_placement(net, mesh, placement: Dict[int, dict]) -> dict:
    """Carry a `plan_weight_placement` decision out on the resident
    cache entries: SHARD layers are `device_put` partitioned over the
    mesh's data axis (last weight dim divisible by it; the divisibility
    fallback replicates, mirroring `distributed.sharding`), REPLICATE
    layers are explicitly replicated.  A no-op on degenerate (single-
    device) meshes.  Returns ``{sharded, replicated, skipped}`` counts.
    """
    counts = {"sharded": 0, "replicated": 0, "skipped": 0}
    ndata = _data_axis_size(mesh)
    if mesh is None or ndata <= 1:
        counts["skipped"] = len(placement)
        return counts
    resident = {k[1]: k for k in net.cache_keys()}
    for layer, decision in placement.items():
        key = resident.get(layer)
        if key is None:
            counts["skipped"] += 1
            continue

        def put(wt, want_shard=(decision["placement"] == SHARD)):
            spec = [None] * wt.ndim
            if want_shard:
                # partition the last dim divisible by the data axis --
                # transform families lay kernels out differently, but
                # all of them keep channel-like dims trailing
                for d in range(wt.ndim - 1, -1, -1):
                    if wt.shape[d] % ndata == 0 and wt.shape[d] >= ndata:
                        spec[d] = "data"
                        break
            return jax.device_put(wt, NamedSharding(mesh, P(*spec)))

        if net.cache.place(key, put):
            sharded = decision["placement"] == SHARD
            counts["sharded" if sharded else "replicated"] += 1
        else:
            counts["skipped"] += 1
    return counts


class ShardedWaveExecutor:
    """One replica's executor, wave-sharded over a mesh's data axis.

    Duck-types `CompiledNet` everywhere the pool and the hot-swap path
    care (`spec`/`cache`/`plan`/`program`/`hw`/`compile_count`/
    `profile_stages`/`cache_keys`), so an elastic pool of sharded
    replicas composes with everything built for plain ones."""

    def __init__(
        self,
        net,
        *,
        shards: int = 1,
        mesh=None,
        placement: Optional[Dict[int, dict]] = None,
    ):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.net = net
        self.shards = shards
        self.mesh = mesh
        self.placement = placement

    # --------------------------------------------------- passthroughs

    @property
    def spec(self):
        return self.net.spec

    @property
    def cache(self):
        return self.net.cache

    @property
    def plan(self):
        return self.net.plan

    @property
    def program(self):
        return self.net.program

    @property
    def hw(self):
        return self.net.hw

    @property
    def compile_count(self) -> int:
        return self.net.compile_count

    def profile_stages(self, x, sizes=None):
        return self.net.profile_stages(x, sizes)

    def cache_keys(self) -> list:
        return self.net.cache_keys()

    def stats(self) -> dict:
        return self.net.stats()

    # ------------------------------------------------------ execution

    def __call__(self, x, sizes=None):
        n = int(x.shape[0])
        if self.shards <= 1 or n <= 1:
            return self.net(x, sizes)
        ndata = _data_axis_size(self.mesh)
        if ndata > 1 and n % ndata == 0:
            # mesh path: rows sharded over the data axis, one program
            # per device
            rows = NamedSharding(self.mesh, P("data"))
            xs = jax.device_put(x, rows)
            ss = None if sizes is None else jax.device_put(sizes, rows)
            return self.net(xs, ss, mesh=self.mesh)
        # logical path: contiguous row groups through the same program,
        # reassembled in order (rows are computed independently and
        # extents ride their rows)
        ys = []
        for lo, hi in shard_bounds(n, self.shards):
            ss = None if sizes is None else sizes[lo:hi]
            ys.append(jnp.asarray(self.net(x[lo:hi], ss)))
        return jnp.concatenate(ys, axis=0)


def probe_image(spec, side: int, *, seed: int = 20240) -> np.ndarray:
    """The fleet's fixed health-probe input: one seeded image at the
    given bucket geometry (deterministic across replicas and runs)."""
    c0 = spec.conv_layers()[0][1].c_in
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((side, side, c0)) * 0.1).astype(np.float32)
