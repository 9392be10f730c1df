"""The parametric Pallas tile kernel: one body for every transform family.

Generalizes the retired bespoke Winograd kernel to any `TileKernelSpec`
(core.transforms): the forward and inverse basis changes enter as *data*
-- the (planes*S, T^2) and (T'^2, planes*S) Kronecker-form matrices --
so Winograd, FFT (re/im split planes) and any future family compile to
the same gather -> fwd GEMM -> batched mix -> inv GEMM -> scatter task
loop.  Per-task intermediates live in two VMEM scratch buffers of S
R-row blocks (the paper's S4.2 shared buffer, split into its left-hand
and product halves).

Every array the kernel touches keeps channels on the lane axis, and
every relayout is a strided VMEM access rather than an in-register
transpose, which is what the TPU compiler accepts:

  * grid step (b, i, j) reads a strip of T padded rows from row i*T' and
    of R*T' + K - 1 columns (rounded to the sublane tile) from column
    j*R*T' -- `pl.Element` offsets, so the overlap-add overlap is read in
    place and never materialized in HBM
  * gather: each tile is T rows of Tp = round_up(T, 8) columns, one
    (T*Tp, C) load whose extra columns meet zero columns of the forward
    basis
  * forward GEMM per tile, (P*S, T*Tp) @ (T*Tp, C); the product's rows
    are stored with stride R, so point s of every tile lands in one
    (R, C) block -- the left-hand matrix of mix s
  * mix: a loop over the S points, one (R, C) @ (C, C') product per
    plane pair against the stationary right-hand matrices (dense and
    block-diagonal for grouped convs, re/im planes combined as complex
    products)
  * inverse GEMM per tile after a stride-R load of its S products, then
    the epilogue (bias/relu from `ElementwiseOps`) and the store of its
    T' x T' output pixels -- fused stages never round-trip intermediates
    through HBM for elementwise glue

Right-hand matrices, basis matrices and bias rows use constant index
maps with a single buffer: fetched once, VMEM-stationary across the
grid.  The kernel's VMEM limit is computed from these blocks
(`vmem_bytes`); geometries above `VMEM_BUDGET_BYTES` are refused here
and excluded by the planner through `kernel_fits`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import transforms

SUBLANES = 8  # second-minor tile of a 32-bit VMEM array
LANES = 128  # minor tile

# The most VMEM one tile-kernel call may claim: half of a v5e core's
# 128 MiB, leaving the rest to XLA's own fusions around the call.
VMEM_BUDGET_BYTES = 64 * 2**20


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_bytes(rows: int, cols: int) -> int:
    """f32 bytes of a (rows, cols) VMEM array padded to the (8, 128) tile."""
    return 4 * _up(rows, SUBLANES) * _up(cols, LANES)


def row_block(r: int) -> int:
    """Tiles per task as the kernel runs them: a whole number of
    sublane tiles, so S blocks of R rows reshape for free."""
    return _up(max(1, r), SUBLANES)


def strip_window(spec: transforms.TileKernelSpec, span: int) -> int:
    """Padded input columns one grid step reads for `span` output
    columns: the K-1 halo plus the Tp - T columns the last tile's
    aligned gather reaches, in whole sublane tiles."""
    return _up(
        span + spec.k - 1 + _up(spec.t, SUBLANES) - spec.t, SUBLANES
    )


def lanes_supported(c: int) -> bool:
    """Channel counts the kernel lays out: one partial lane tile, or
    whole lane tiles."""
    return c <= LANES or c % LANES == 0


def vmem_bytes(
    spec: transforms.TileKernelSpec, c_in: int, c_out: int, r: int,
    tasks_per_program: int = 1,
) -> int:
    """VMEM the kernel's blocks and scratch take, tile padding included:
    double-buffered input strip and output block, single-buffered
    stationary operands, the two shared-buffer halves, and headroom of
    two right-hand blocks and two output tiles for the compiler's own
    temporaries."""
    t, t_out, p, s = spec.t, spec.t_out, spec.planes, spec.s_mix
    tp, tq = _up(t, SUBLANES), _up(t_out, SUBLANES)
    r = row_block(r)
    span = tasks_per_program * r * t_out
    rhs_block = _tile_bytes(c_in, c_out)
    return (
        2 * t * _tile_bytes(strip_window(spec, span), c_in)  # input strip
        + 2 * t_out * _tile_bytes(span, c_out)  # output block
        + p * s * rhs_block  # right-hand matrices
        + _tile_bytes(p * s, t * tp)  # forward basis
        + _tile_bytes(t_out * tq, p * s)  # inverse basis
        + 4 * _tile_bytes(1, c_out)  # bias rows
        + _tile_bytes(p * s * r, c_in)  # left-hand half
        + _tile_bytes(p * s * r, c_out)  # product half
        + 2 * rhs_block
        + 2 * _tile_bytes(t_out * tq, c_out)
    )


def kernel_fits(
    spec: transforms.TileKernelSpec, c_in: int, c_out: int, r: int,
    tasks_per_program: int = 1,
) -> bool:
    """Whether one call at this geometry can be laid out and stays
    within the VMEM budget (what the planner asks before it plans)."""
    return (
        lanes_supported(c_in)
        and lanes_supported(c_out)
        and vmem_bytes(spec, c_in, c_out, r, tasks_per_program)
        <= VMEM_BUDGET_BYTES
    )


def _padded_bases(spec: transforms.TileKernelSpec):
    """The basis matrices in the kernel's aligned layouts: forward
    columns indexed (row, col < Tp) with zeros for col >= T; inverse
    rows indexed (row, col < Tq) with zeros for col >= T'."""
    t, t_out = spec.t, spec.t_out
    tp, tq = _up(t, SUBLANES), _up(t_out, SUBLANES)
    ps = spec.planes * spec.s_mix
    kf = jnp.asarray(spec.fwd).reshape(ps, t, t)
    kf = jnp.pad(kf, ((0, 0), (0, 0), (0, tp - t))).reshape(ps, t * tp)
    ki = jnp.asarray(spec.inv).reshape(t_out, t_out, ps)
    ki = jnp.pad(ki, ((0, 0), (0, tq - t_out), (0, 0)))
    return kf, ki.reshape(t_out * tq, ps)


# Matmul precision of every tile-engine GEMM, set where it is computed:
# at the default precision the v5e contracts float32 operands in
# reduced (bfloat16) passes, which Winograd F(5,3)'s basis amplifies to
# ~0.15 relative error per layer, and FFT T=16 layers show ~4e-3 (chip
# run on a TPU v5e); full float32 passes hold ~6e-6.
GEMM_PRECISION = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=GEMM_PRECISION,
        preferred_element_type=jnp.float32,
    )


def lane_chunks(c: int):
    """(lo, hi) lane ranges of at most 128 channels: the kernel's VMEM
    intermediates hold one chunk per leading index, because the
    compiler's strided accesses reach a single lane tile."""
    return [(lo, min(c, lo + LANES)) for lo in range(0, c, LANES)]


def _apply_ep(y, ep_ops, biases_ref, lo, hi):
    """Static epilogue op list on (rows, hi - lo) output channels; biases
    are rows of the stationary biases input."""
    for op in ep_ops:
        if op[0] == "bias":
            y = y + biases_ref[pl.ds(op[1], 1), pl.ds(lo, hi - lo)]
        else:  # relu
            y = jnp.maximum(y, 0.0)
    return y


def _kernel_body(
    x_ref, rhs_ref, kf_ref, ki_ref, biases_ref, o_ref, lhs_ref, prod_ref,
    *,
    spec: transforms.TileKernelSpec,
    r: int,
    tasks_per_program: int,
    ep_ops: tuple,
):
    t, t_out, p, s = spec.t, spec.t_out, spec.planes, spec.s_mix
    tp, tq = _up(t, SUBLANES), _up(t_out, SUBLANES)
    c_in, c_out = x_ref.shape[-1], o_ref.shape[-1]
    in_chunks, out_chunks = lane_chunks(c_in), lane_chunks(c_out)
    kf = kf_ref[...]  # (P*S, T*Tp) forward basis
    ki = ki_ref[...]  # (T'*Tq, P*S) inverse basis

    def mix(si, carry):
        def block(q):  # the R rows of point si on plane q
            return pl.ds(pl.multiple_of((q * s + si) * r, SUBLANES), r)

        # left-hand rows of point si, per plane, per input lane chunk
        rows = [
            [lhs_ref[k, block(q), :] for k in range(len(in_chunks))]
            for q in range(p)
        ]

        def mm(q, w_plane):  # (R, C) @ (C, C') over input lane chunks
            acc = None
            for (lo, hi), lh in zip(in_chunks, rows[q]):
                part = _dot(lh, rhs_ref[w_plane, si, pl.ds(lo, hi - lo), :])
                acc = part if acc is None else acc + part
            return acc

        if p == 1:
            outs = [mm(0, 0)]
        else:  # complex product on (re, im) planes
            outs = [mm(0, 0) - mm(1, 1), mm(0, 1) + mm(1, 0)]
        for q, out in enumerate(outs):
            for k, (lo, hi) in enumerate(out_chunks):
                prod_ref[k, block(q), :] = out[:, lo:hi]
        return carry

    for task in range(tasks_per_program):
        # -- step 1: gather each tile (T rows of Tp columns) and forward-
        # transform it; stride-R stores put point s of all R tiles in
        # rows [s*R, (s+1)*R) -- the left-hand matrix of mix s
        for i in range(r):
            off = (task * r + i) * t_out
            d = x_ref[0, :, pl.ds(off, tp), :].astype(jnp.float32)
            u = _dot(kf, d.reshape(t * tp, c_in))  # (P*S, C)
            for k, (lo, hi) in enumerate(in_chunks):
                lhs_ref[k, pl.ds(i, p * s, stride=r), :] = u[:, lo:hi]

        # -- step 2: S channel-mix GEMMs against the stationary
        # right-hand matrices
        jax.lax.fori_loop(0, s, mix, 0)

        # -- step 3: per tile, gather its S products, inverse-transform,
        # apply the epilogue and store its T' x T' output pixels
        for i in range(r):
            off = (task * r + i) * t_out
            for k, (lo, hi) in enumerate(out_chunks):
                z = prod_ref[k, pl.ds(i, p * s, stride=r), :]  # (P*S, C')
                y = _apply_ep(_dot(ki, z), ep_ops, biases_ref, lo, hi)
                y = y.reshape(t_out, tq, hi - lo)[:, :t_out, :]
                o_ref[0, :, pl.ds(off, t_out), pl.ds(lo, hi - lo)] = (
                    y.astype(o_ref.dtype)
                )


def fused_tile_call(
    xp: jnp.ndarray,
    rhs: jnp.ndarray,
    biases: jnp.ndarray,
    *,
    spec: transforms.TileKernelSpec,
    n_tiles_h: int,
    n_tiles_w: int,
    r: int,
    tasks_per_program: int = 1,
    ep_ops: tuple = (),
    interpret: bool = True,
) -> jnp.ndarray:
    """Invoke the parametric fused kernel.

    xp:  (B, H_pad, W_pad, C) pre-padded input, H_pad = nH*T' + K - 1,
         W_pad = nW*T' + K - 1, nW divisible by row_block(r) *
         tasks_per_program.
    rhs: (P, S, C, C') dense right-hand matrices
         (`TileKernelSpec.pack_planes`).
    biases: (n_bias, C') rows referenced by ("bias", idx) epilogue ops
         (pass shape (1, C') zeros when unused).
    returns: (B, nH*T', nW*T', C') assembled output tiles.
    """
    b, h_pad, w_pad, c_in = xp.shape
    t, t_out, p, s = spec.t, spec.t_out, spec.planes, spec.s_mix
    c_out = rhs.shape[-1]
    r = row_block(r)
    tpp = max(1, tasks_per_program)
    span = tpp * r * t_out
    if n_tiles_w % (r * tpp):
        raise ValueError(f"{n_tiles_w} column tiles do not divide {r}x{tpp}")
    if h_pad != n_tiles_h * t_out + spec.k - 1:
        raise ValueError(f"padded height {h_pad} != {n_tiles_h} tiles")
    if w_pad != n_tiles_w * t_out + spec.k - 1:
        raise ValueError(f"padded width {w_pad} != {n_tiles_w} tiles")
    need = vmem_bytes(spec, c_in, c_out, r, tpp)
    if not kernel_fits(spec, c_in, c_out, r, tpp):
        raise ValueError(
            f"tile kernel cannot hold C={c_in}->{c_out}, T={t}, R={r}: it "
            f"needs {need} B of VMEM (budget {VMEM_BUDGET_BYTES} B) and "
            "channels in one partial or whole lane tiles"
        )
    n_col_blocks = n_tiles_w // (r * tpp)
    window = strip_window(spec, span)
    width = (n_col_blocks - 1) * span + window
    xp = jnp.pad(xp, ((0, 0), (0, 0), (0, width - w_pad), (0, 0)))
    kf, ki = _padded_bases(spec)

    body = functools.partial(
        _kernel_body, spec=spec, r=r, tasks_per_program=tpp,
        ep_ops=tuple(ep_ops),
    )
    const = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda bi, i, j: (0,) * len(shape),
        pipeline_mode=pl.Buffered(1),
    )
    scratch = lambda c: pltpu.VMEM(  # noqa: E731
        (len(lane_chunks(c)), p * s * r, min(c, LANES)), jnp.float32
    )
    return pl.pallas_call(
        body,
        grid=(b, n_tiles_h, n_col_blocks),
        in_specs=[
            # element-offset strip: T rows at stride T' and `window`
            # columns at stride `span` (the OLA overlap, read in place)
            pl.BlockSpec(
                (pl.Element(1), pl.Element(t), pl.Element(window),
                 pl.Element(c_in)),
                lambda bi, i, j: (bi, i * t_out, j * span, 0),
            ),
            const(*rhs.shape),  # stationary right-hand matrices
            const(*kf.shape),  # forward basis
            const(*ki.shape),  # inverse basis
            const(*biases.shape),
        ],
        out_specs=pl.BlockSpec(
            (1, t_out, span, c_out), lambda bi, i, j: (bi, i, j, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_tiles_h * t_out, n_tiles_w * t_out, c_out), xp.dtype
        ),
        scratch_shapes=[scratch(c_in), scratch(c_out)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(need + need // 4, VMEM_BUDGET_BYTES)
        ),
        interpret=interpret,
        name=f"convserve_tile_{spec.family}_t{t}",
    )(xp, rhs, kf, ki, biases)
