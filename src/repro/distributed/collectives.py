"""Compressed gradient collectives (shard_map) with error feedback.

int8 block-quantised all-reduce: each worker quantises its local gradient
shard to int8 (per-block f32 scales), all-reduces the int8 payload (summed
in int32), dequantises, and keeps the quantisation residual locally, adding
it to the next step's gradient (error feedback) -- bandwidth drops ~4x
vs f32 / ~2x vs bf16 at negligible quality cost.  Used on the `data`/`pod`
gradient-reduction axes; opt-in via TrainConfig in examples/train_lm.py.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any

_BLOCK = 256


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    flat = x.reshape(-1)
    pad = (-flat.size) % _BLOCK
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, _BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(scale, 1e-20)).astype(jnp.int8)
    return q, scale[:, 0]


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray, shape, size) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return flat[:size].reshape(shape)


def compressed_psum(
    grad: jnp.ndarray, residual: jnp.ndarray, axis_name
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-tensor int8 all-reduce with error feedback, inside shard_map.

    Returns (mean gradient, new residual)."""
    g = grad.astype(jnp.float32) + residual
    flat = g.reshape(-1)
    pad = (-flat.size) % _BLOCK
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, _BLOCK)
    # agree on one scale per block across workers (pmax of f32 scales is
    # tiny traffic), then the int8 payload psum aggregates EXACTLY
    local_max = jnp.max(jnp.abs(blocks), axis=1)
    scale = jax.lax.pmax(local_max, axis_name) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.round(blocks / scale[:, None]).astype(jnp.int8)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    red = (qsum.astype(jnp.float32) / n) * scale[:, None]
    g_red = red.reshape(-1)[: g.size].reshape(g.shape)
    # error feedback: this worker's own quantisation error feeds step t+1
    deq_local = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    new_residual = g - deq_local[: g.size].reshape(g.shape)
    return g_red, new_residual


def _requant_roundtrip(g: jnp.ndarray) -> jnp.ndarray:
    q, scale = _quantize(g)
    return _dequantize(q, scale, g.shape, g.size)


def make_compressed_allreduce(mesh: Mesh, axis: str = "data"):
    """Tree-level compressed mean-all-reduce over `axis` via shard_map.

    Inputs are sharded over `axis` on their leading dim (one slice per
    worker = that worker's local gradient); every worker's output slice is
    the compressed mean, residuals stay worker-local (error feedback).
    """

    def one(g, r):
        fn = jax.shard_map(
            functools.partial(compressed_psum, axis_name=axis),
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )
        return fn(g, r)

    def allreduce(grads: Pytree, residuals: Pytree) -> Tuple[Pytree, Pytree]:
        flat_g, treedef = jax.tree.flatten(grads)
        flat_r = treedef.flatten_up_to(residuals)
        out_g, out_r = [], []
        for g, r in zip(flat_g, flat_r):
            gg, rr = one(g, r)
            out_g.append(gg)
            out_r.append(rr)
        return (
            jax.tree.unflatten(treedef, out_g),
            jax.tree.unflatten(treedef, out_r),
        )

    return allreduce


def init_residuals(params: Pytree) -> Pytree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
