"""Jit'd public wrappers for the Pallas kernels, including pytree plumbing
so the protocol layer can call the fused aggregation on whole model trees.

Two tree-level aggregation paths are exposed:

* ``safa_aggregate_tree``        — one kernel dispatch per pytree leaf;
* ``safa_aggregate_tree_packed`` — the model is flattened once into a single
  [m, N_total] buffer (ragged leaves laid out at per-leaf offsets, padded
  once at the end to a tile multiple), so Eq. 6-8 runs as exactly one
  ``pallas_call`` per round regardless of model depth.

Fleets of S servers batch these under ``jax.vmap``: inside
``protocol.safa_run_fleet`` the per-round ``safa_aggregate_packed`` call is
batched by JAX's vmap rule into one launch over a grid with a leading
fleet dimension (``pack_fleet``/``unpack_fleet`` lay out [S, m, N]).
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.protocol import AggregationResult
from repro.kernels import backend
from repro.kernels.backend import LANES, SUBLANES, fit_tile, padded_rows
from repro.kernels.comm_quant import (QBLOCK, dequantize, dequantize_packed,
                                      quantize, quantize_packed)
from repro.kernels.safa_aggregate import (DEFAULT_TILE, as_slab,
                                          check_rows, check_slab_tile,
                                          check_width, group_spec,
                                          pipelined_bytes, read_slab_row,
                                          row_of,
                                          safa_aggregate,
                                          safa_aggregate_packed,
                                          safa_aggregate_packed_q8,
                                          safa_aggregate_packed_q8_rows,
                                          safa_aggregate_packed_q8_tier_rows,
                                          safa_aggregate_packed_rows,
                                          safa_aggregate_packed_tier_rows,
                                          set_row, slab_width, slot_spec,
                                          tier_tile, write_row)
from repro.kernels.swa_attention import swa_attention

__all__ = ['safa_aggregate', 'safa_aggregate_packed', 'safa_aggregate_tree',
           'safa_aggregate_tree_packed', 'safa_aggregate_packed_q8',
           'safa_aggregate_packed_rows', 'safa_aggregate_packed_q8_rows',
           'safa_aggregate_packed_tier_rows',
           'safa_aggregate_packed_q8_tier_rows',
           'gather_rows', 'scatter_rows', 'as_slab',
           'quantize', 'dequantize', 'quantize_packed', 'dequantize_packed',
           'safa_compressed_update',
           'weighted_merge_packed', 'weighted_merge_tree_packed',
           'wire_roundtrip_packed', 'wire_spec',
           'swa_attention', 'quantize_tree', 'dequantize_tree',
           'PackSpec', 'pack_spec', 'pack_stacked', 'pack_global',
           'pack_fleet', 'unpack_fleet',
           'unpack_stacked', 'unpack_global', 'comm_bytes',
           'count_pallas_calls']


def count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call eqns in a jaxpr — the number of kernel
    dispatches one execution of the traced function will issue (used by the
    dispatch-count benchmark and its regression test).  Descends into
    nested jaxprs held directly, as ClosedJaxprs, or in tuple params
    (e.g. lax.cond ``branches``)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            n += 1
        for p in eqn.params.values():
            for v in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(v, 'eqns'):                       # Jaxpr
                    n += count_pallas_calls(v)
                elif hasattr(getattr(v, 'jaxpr', None), 'eqns'):  # ClosedJaxpr
                    n += count_pallas_calls(v.jaxpr)
    return n


def safa_aggregate_tree(cache, trained, global_prev, *, picked, undrafted,
                        deprecated, weights) -> AggregationResult:
    """Apply the fused Eq. 6-8 kernel leaf-by-leaf over stacked pytrees.

    cache/trained: pytrees with leading clients dim m; global_prev: pytree.
    """
    def one(c, t, g):
        m = c.shape[0]
        ng, nc = safa_aggregate(
            c.reshape(m, -1), t.reshape(m, -1), g.reshape(-1).astype(c.dtype),
            picked, undrafted, deprecated, weights)
        return ng.reshape(g.shape).astype(g.dtype), nc.reshape(c.shape)

    flat_c, treedef = jax.tree_util.tree_flatten(cache)
    flat_t = jax.tree_util.tree_flatten(trained)[0]
    flat_g = jax.tree_util.tree_flatten(global_prev)[0]
    outs = [one(c, t, g) for c, t, g in zip(flat_c, flat_t, flat_g)]
    new_global = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_cache = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return AggregationResult(new_global, new_cache)


# ---------------------------------------------------------------------------
# Packed layout: whole model as one [*, N_total] buffer
# ---------------------------------------------------------------------------

class PackSpec(NamedTuple):
    """Static layout of a model pytree inside a flat pack buffer.

    ``offsets[i]:offsets[i] + sizes[i]`` holds leaf i (global shapes, i.e.
    without the clients dim); each leaf's slot is zero-padded up to the
    next leaf's offset (slots only exceed sizes under ``align > 1``);
    ``n_padded`` is the laid-out total rounded up to a tile multiple so
    kernels never re-pad per call."""
    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    offsets: tuple
    n_total: int
    n_padded: int

    def slot(self, i: int) -> int:
        """Width of leaf i's slot (its size plus alignment padding)."""
        nxt = self.offsets[i + 1] if i + 1 < len(self.offsets) \
            else self.n_total
        return nxt - self.offsets[i]


def pack_spec(global_tree, *, pad_to: int = DEFAULT_TILE,
              align: int = 1) -> PackSpec:
    """Build the layout from a *global* (unstacked) model pytree.

    ``align > 1`` rounds every leaf's slot up to an ``align`` multiple so
    leaf boundaries never share a block — the quantized wire format uses
    ``align=QBLOCK`` so packed per-QBLOCK scales match per-leaf
    quantisation bit for bit (see ``wire_spec``).

    ``pad_to`` must be a multiple of ``align``: the final tile padding is
    itself a run of alignment blocks, so a non-multiple would leave the
    last quantisation block straddling the buffer end (scales row shorter
    than the data row) and the kernels' ``n_padded // align`` reshapes
    would silently misalign."""
    if pad_to < 1 or align < 1:
        raise ValueError(
            f'pack_spec needs pad_to >= 1 and align >= 1, got '
            f'pad_to={pad_to}, align={align}')
    if pad_to % align:
        raise ValueError(
            f'pad_to={pad_to} is not a multiple of align={align}: the tile '
            'padding must consist of whole alignment blocks (pick pad_to as '
            'a multiple of align, or drop the alignment)')
    leaves, treedef = jax.tree_util.tree_flatten(global_tree)
    shapes = tuple(l.shape for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(l.size) for l in leaves)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s + ((-s) % align)
    n_padded = off + ((-off) % pad_to)
    return PackSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    sizes=sizes, offsets=tuple(offsets), n_total=off,
                    n_padded=n_padded)


def wire_spec(global_tree, *, pad_to: int = DEFAULT_TILE) -> PackSpec:
    """The pack layout of the int8 wire format: QBLOCK-aligned leaf slots,
    so every quantisation block lies inside exactly one leaf of exactly one
    client row."""
    return pack_spec(global_tree, pad_to=pad_to, align=QBLOCK)


def _pack(leaves, lead_shape, spec: PackSpec, compute_dtype):
    flat = []
    for i, (l, size) in enumerate(zip(leaves, spec.sizes)):
        x = l.astype(compute_dtype).reshape(lead_shape + (-1,))
        gap = spec.slot(i) - size
        if gap:
            x = jnp.pad(x, [(0, 0)] * len(lead_shape) + [(0, gap)])
        flat.append(x)
    pad = spec.n_padded - spec.n_total
    if pad:
        flat.append(jnp.zeros(lead_shape + (pad,), compute_dtype))
    return jnp.concatenate(flat, axis=-1)


def pack_stacked(tree, spec: PackSpec, *, dtype=jnp.float32):
    """Stacked pytree ([m, ...] leaves) -> [m, n_padded] buffer."""
    leaves = jax.tree_util.tree_leaves(tree)
    m = leaves[0].shape[0]
    return _pack(leaves, (m,), spec, dtype)


def pack_global(tree, spec: PackSpec, *, dtype=jnp.float32):
    """Global pytree -> [n_padded] buffer."""
    return _pack(jax.tree_util.tree_leaves(tree), (), spec, dtype)


def _unpack(buf, spec: PackSpec, lead_shape):
    outs = []
    for shape, dt, size, off in zip(spec.shapes, spec.dtypes, spec.sizes,
                                    spec.offsets):
        leaf = buf[..., off:off + size].reshape(lead_shape + shape)
        outs.append(leaf.astype(dt))
    return jax.tree_util.tree_unflatten(spec.treedef, outs)


def unpack_stacked(buf, spec: PackSpec):
    """[m, n_padded] buffer -> stacked pytree."""
    return _unpack(buf, spec, (buf.shape[0],))


def unpack_global(buf, spec: PackSpec):
    """[n_padded] buffer -> global pytree."""
    return _unpack(buf, spec, ())


def pack_fleet(tree, spec: PackSpec, *, dtype=jnp.float32):
    """Fleet-stacked pytree ([S, m, ...] leaves) -> [S, m, n_padded] buffer.

    Fleet-stacked *global* trees ([S, ...] leaves) pack with
    ``pack_stacked`` — the leading axis is just S instead of m."""
    leaves = jax.tree_util.tree_leaves(tree)
    return _pack(leaves, leaves[0].shape[:2], spec, dtype)


def unpack_fleet(buf, spec: PackSpec):
    """[S, m, n_padded] buffer -> fleet-stacked pytree."""
    return _unpack(buf, spec, buf.shape[:2])


# ---------------------------------------------------------------------------
# Rows gather/scatter: the train-side pack path of sparse schedules
# ---------------------------------------------------------------------------
#
# Sparse engines keep the per-client state as one resident [m+1, n_padded]
# pack buffer (the trailing scratch row absorbs sentinel slots, idx == m)
# and move only the K = O(quota) active rows per round: ``gather_rows``
# pulls them out for local training, ``scatter_rows`` writes results back
# in place (the buffer is aliased to the output, so untouched rows are
# never copied).  Both use the same scalar-prefetch indexing and 8-row
# group blocks as the rows-aggregation kernels in ``safa_aggregate``.
#
# The lag-tier engines keep their value buffer as a row slab [R, N/128,
# 128] (see the lag-tier block of ``safa_aggregate``), whose rows are
# whole (8, 128) tiles: ``gather_rows`` given a slab copies exactly each
# active row by one DMA, at the tier kernels' column tile, into the 8-row
# output groups of the [K, N] result.


#: Static alias inventory for this module's pallas kernels (see
#: ``safa_aggregate.ALIAS_CONTRACTS`` for the format): the scatter
#: kernel aliases the row buffer to the output — untouched rows never
#: move — and everything else is copy-out.  ``repro.analysis`` checks
#: this dict against the call sites (REP005) and lowered cells (JAX003).
ALIAS_CONTRACTS = {
    '_copy_kernel': ((),),
    '_slab_copy_kernel': ((),),
    '_scatter_kernel': (((2, 0),),),        # buf -> out (rows prefetched)
    '_weighted_merge_kernel': ((),),
}


def _copy_kernel(rows_ref, src_ref, dst_ref):
    j = pl.program_id(1)
    set_row(dst_ref, j, row_of(src_ref, rows_ref[j]))


def _slab_copy_kernel(rows_ref, buf_ref, out_ref, row, sem):
    i, j = pl.program_id(0), pl.program_id(1)
    set_row(out_ref, j,
            read_slab_row(buf_ref, row, sem, rows_ref[j], i).reshape(1, -1))


#: VMEM a slab gather's grid step needs, in bytes a lane column of its
#: tile (the output group, the scratch row and the row reshaped), found
#: as ``safa_aggregate.TIER_VMEM_BYTES`` is
GATHER_VMEM_BYTES = 92


def _gather_slab(buf, rows, tile):
    """``gather_rows`` of a row slab: a (N // tile, K) grid whose steps
    each copy one row's column tile by one DMA; records the call's DMAs
    and bytes under ``gather_rows`` in ``obs.counters()``, as the
    tier-rows kernels do (every slot issues one, so the count is
    exact)."""
    n, k = slab_width(buf.shape), rows.shape[0]
    params = None
    if tile is None:        # tier_tile's measure counts each byte twice
        tile, params = tier_tile(n, GATHER_VMEM_BYTES // 2)
    check_slab_tile(n, tile)
    grid = (n // tile, k)           # k innermost: output groups stay put
    out_spec = slot_spec(tile)
    out_shape = jax.ShapeDtypeStruct((k, n), buf.dtype)
    dmas = math.prod(grid)
    obs.count('gather_rows', dmas=dmas,
              bytes=dmas * tile * jnp.dtype(buf.dtype).itemsize
              + pipelined_bytes(grid, [out_spec], [out_shape]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((tile // LANES, LANES), buf.dtype),
                        pltpu.SemaphoreType.DMA])
    return pl.pallas_call(
        _slab_copy_kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=params,
        interpret=backend.interpret())(rows.astype(jnp.int32), buf)


def _scatter_kernel(rows_ref, vals_ref, buf_ref, out_ref, grp, sem):
    del buf_ref  # aliased: read and written through out_ref
    i, j = pl.program_id(0), pl.program_id(1)
    write_row(out_ref, grp, sem, rows_ref[j], i, vals_ref.shape[1],
              row_of(vals_ref, j))


@functools.partial(jax.jit, static_argnames=('tile',))
def gather_rows(buf, rows, *, tile: int | None = None):
    """buf [R, N], or a row slab [R, N/128, 128]; rows [K] int32 < R ->
    [K, N] gathered rows, in one dispatch.  From [R, N] only the K rows'
    8-row groups stream through, at ``tile`` (``DEFAULT_TILE`` by
    default); from a slab each row is copied exactly, at ``tile`` or by
    default the widest that ``tier_tile`` allows."""
    if buf.ndim == 3:
        return _gather_slab(buf, rows, tile)
    tile = tile or DEFAULT_TILE
    _, n = buf.shape
    k = rows.shape[0]
    check_width(n, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tile, k),        # k innermost: output groups stay put
        in_specs=[group_spec(tile, lambda j, rows: rows[j])],
        out_specs=slot_spec(tile))
    return pl.pallas_call(
        _copy_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, n), buf.dtype),
        interpret=backend.interpret())(rows.astype(jnp.int32), buf)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=('tile',))
def scatter_rows(buf, rows, vals, *, tile: int = DEFAULT_TILE):
    """Write vals [K, N] into buf [R, N] at ``rows`` and return the buffer
    (donated + aliased: untouched rows stay in place, no [R, N] copy).

    Each row is written by a read-modify-write of its 8-row group, so R
    must be a multiple of 8 (``backend.row_pad``).  Duplicate row indices
    write in slot order (last wins); sentinel slots should point at a
    scratch row (idx = m of an ``row_pad(m + 1)``-row buffer) so padding
    writes land harmlessly."""
    r, n = buf.shape
    k = rows.shape[0]
    if vals.shape != (k, n):
        raise ValueError(
            f'vals shape {vals.shape} does not match (K={k}, N={n})')
    check_width(n, tile)
    check_rows(r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tile, k),
        in_specs=[slot_spec(tile), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((SUBLANES, tile), buf.dtype),
                        pltpu.SemaphoreType.DMA])
    return pl.pallas_call(
        _scatter_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), buf.dtype),
        # operand 0 is the prefetched rows, so buf is input index 2
        input_output_aliases={2: 0},
        interpret=backend.interpret())(rows.astype(jnp.int32), vals, buf)


def safa_aggregate_tree_packed(cache, trained, global_prev, *, picked,
                               undrafted, deprecated, weights,
                               spec: PackSpec = None) -> AggregationResult:
    """Single-dispatch Eq. 6-8 over a whole model pytree.

    Flattens the three operand trees into pack buffers (a fusion-friendly
    concat, no kernel launches), runs ``safa_aggregate_packed`` exactly
    once, and unpacks the results.  ``spec`` may be precomputed by callers
    that aggregate every round (the layout only depends on the model).

    The pack buffer computes in float32, so only float32 models are
    accepted — other dtypes would silently diverge from the leaf-wise
    path (which computes in each leaf's own dtype); use
    ``safa_aggregate_tree`` for those."""
    if spec is None:
        spec = pack_spec(global_prev)
    _require_f32(spec)
    pc = pack_stacked(cache, spec)
    pt = pack_stacked(trained, spec)
    pg = pack_global(global_prev, spec)
    ng, nc = safa_aggregate_packed(pc, pt, pg, picked, undrafted, deprecated,
                                   weights)
    return AggregationResult(unpack_global(ng, spec), unpack_stacked(nc, spec))


def _require_f32(spec: PackSpec):
    bad = [str(d) for d in spec.dtypes if d != jnp.float32]
    if bad:
        raise TypeError(
            f'packed aggregation requires float32 leaves, got {bad}; use '
            'the leaf-wise safa_aggregate_tree for mixed/low-precision '
            'models')


# ---------------------------------------------------------------------------
# Weighted-merge kernel: the staleness-adaptive aggregation family's
# server step as one fused dispatch
# ---------------------------------------------------------------------------

def _weighted_merge_kernel(trained_ref, global_ref, w_ref, out_ref):
    """One [m, T] tile of  (1 - sum(w)) * g + sum_k w_k * t_k.

    ``w`` carries the whole aggregation scheme: SEAFL's adaptive
    staleness weights arrive pre-normalised, and CSAFL's per-cluster
    sub-aggregates arrive pre-folded (w_k = alpha_g * what_k, zero off
    the cluster's committed set) — the masked cluster reduction happens
    implicitly through the zeros, so one operand serves every scheme."""
    g = global_ref[...]                               # [1, T]
    w = w_ref[...].astype(jnp.float32)                # [m, 1]
    residual = 1.0 - jnp.sum(w)
    agg = jnp.sum(trained_ref[...].astype(jnp.float32) * w, axis=0,
                  keepdims=True)
    out_ref[...] = (residual * g.astype(jnp.float32) + agg).astype(g.dtype)


@functools.partial(jax.jit, static_argnames=('tile',))
def weighted_merge_packed(trained, global_prev, wrow, *,
                          tile: int = DEFAULT_TILE):
    """Single fused weighted-merge dispatch on pre-padded pack buffers.

    trained: [m, N] packed client uploads (N % tile == 0, see
    ``pack_stacked``); global_prev: [N]; wrow: [m] f32 effective merge
    weights (0 for non-commits, sum <= 1).  One ``pallas_call`` over the
    N // tile grid computes ``(1 - sum(wrow)) * global + wrow @ trained``
    regardless of model depth; under the fleet engine's vmap the launch
    batches into an (S, tiles) grid.  Returns the new global row [N]."""
    m, np_ = trained.shape
    check_width(np_, tile)
    # trained block plus the global and output rows
    tile, params = fit_tile(tile, 4 * padded_rows(m, 4) + 2 * 4 * SUBLANES)
    out = pl.pallas_call(
        _weighted_merge_kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((m, tile), lambda i: (0, i)),      # trained
            pl.BlockSpec((1, tile), lambda i: (0, i)),      # global
            pl.BlockSpec((m, 1), lambda i: (0, 0)),         # wrow
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), trained.dtype),
        compiler_params=params,
        interpret=backend.interpret(),
    )(trained, global_prev.reshape(1, -1),
      wrow.astype(jnp.float32).reshape(m, 1))
    return out[0]


def weighted_merge_tree_packed(trained, global_prev, *, wrow,
                               spec: PackSpec = None):
    """Single-dispatch weighted merge over a whole model pytree.

    Flattens the trained stack and the global tree into pack buffers (a
    fusion-friendly concat, no kernel launches), runs
    ``weighted_merge_packed`` exactly once, and unpacks the new global.
    ``spec`` may be precomputed by callers that merge every round (the
    layout only depends on the model).  Float32-only, like the other
    packed paths."""
    if spec is None:
        spec = pack_spec(global_prev)
    _require_f32(spec)
    pt = pack_stacked(trained, spec)
    pg = pack_global(global_prev, spec)
    return unpack_global(weighted_merge_packed(pt, pg, wrow), spec)


def quantize_tree(tree):
    """Quantise every leaf (for communication-compressed uploads)."""
    return jax.tree.map(lambda x: quantize(x.reshape(-1)), tree)


def dequantize_tree(qtree, like):
    flat_l, treedef = jax.tree_util.tree_flatten(like)
    # flatten qtree only down to ``like``'s structure so each (q, scales)
    # pair stays intact — robust even when ``like`` itself contains tuples
    flat_q = treedef.flatten_up_to(qtree)
    outs = [dequantize(q, s, n=l.size).reshape(l.shape).astype(l.dtype)
            for (q, s), l in zip(flat_q, flat_l)]
    return jax.tree_util.tree_unflatten(treedef, outs)


# ---------------------------------------------------------------------------
# Compressed wire path: packed int8 uplink in 2 dispatches total
# ---------------------------------------------------------------------------

def safa_compressed_update(base, trained, cache, global_prev, *, picked,
                           undrafted, deprecated, completed, weights,
                           spec: PackSpec = None):
    """One SAFA server step on the int8 wire: quantize + fused
    dequant-aggregate, exactly TWO kernel dispatches for any model depth.

    base/trained/cache: stacked pytrees ([m, ...] leaves); global_prev:
    global pytree; picked/undrafted/deprecated/completed: [m] bool;
    weights: [m] f32.  The trained tree is packed once
    (QBLOCK-aligned layout), block-quantised in one grid dispatch
    (``quantize_packed`` — the simulated uplink carries int8 + scales),
    and ``safa_aggregate_packed_q8`` dequantises it in-register while
    applying Eq. 6-8 with the cache buffer aliased.  Crashed clients'
    rows are replaced by their base model inside the kernel (no upload
    arrived).  Returns (new_global, new_local, new_cache) pytrees —
    the same triple ``protocol.safa_round`` hands back.

    Bit-identical to the per-leaf reference (each client quantising each
    leaf with ``quantize``/``dequantize`` before a packed aggregation):
    the QBLOCK-aligned layout keeps every quantisation block inside one
    leaf of one client row, so the scales — and therefore every
    dequantised value — agree exactly.
    """
    if spec is None:
        spec = wire_spec(global_prev)
    _require_f32(spec)
    with obs.scope('wire'):
        q, scales = quantize_packed(pack_stacked(trained, spec))
    with obs.scope('aggregate'):
        ng, nc, nl = safa_aggregate_packed_q8(
            q, scales, pack_stacked(base, spec), pack_stacked(cache, spec),
            pack_global(global_prev, spec), picked, undrafted, deprecated,
            completed, weights)
        return (unpack_global(ng, spec), unpack_stacked(nl, spec),
                unpack_stacked(nc, spec))


def wire_roundtrip_packed(tree, spec: PackSpec = None, *, like=None):
    """Simulate the int8 wire for a whole stacked pytree in 2 dispatches:
    pack -> ``quantize_packed`` -> ``dequantize_packed`` -> unpack.

    Used by protocols without a fused aggregation kernel (FedAvg/FedCS):
    the server sees exactly what a compressed transfer delivers, at
    packed-dispatch cost instead of 2 dispatches per leaf per client.
    ``like`` supplies the global tree for spec inference (defaults to the
    first client's row of ``tree``)."""
    if spec is None:
        if like is None:
            like = jax.tree.map(lambda a: a[0], tree)
        spec = wire_spec(like)
    _require_f32(spec)
    buf = pack_stacked(tree, spec)
    q, scales = quantize_packed(buf)
    return unpack_stacked(dequantize_packed(q, scales), spec)


def comm_bytes(tree, quantized: bool, *, layout: str = 'tree') -> int:
    """Bytes on the wire for one model transfer (benchmark accounting).

    ``layout='tree'`` counts the pytree leaves as shipped individually
    (per-leaf scale ceilings, no padding); ``layout='packed'`` counts the
    packed wire buffers as the fast path actually ships them — including
    the QBLOCK alignment / tile padding and the full scale rows of the
    quantized format, or the tile padding of a f32 pack."""
    if layout not in ('tree', 'packed'):
        raise ValueError(
            f"unknown layout {layout!r} (want 'tree' or 'packed')")
    leaves = jax.tree.leaves(tree)
    if layout == 'packed':
        spec = wire_spec(tree) if quantized else pack_spec(tree)
        if not quantized:
            return 4 * spec.n_padded
        return spec.n_padded + 4 * (spec.n_padded // QBLOCK)
    n = sum(l.size for l in leaves)
    if not quantized:
        return sum(l.size * l.dtype.itemsize for l in leaves)
    return n + 4 * sum(-(-l.size // QBLOCK) for l in leaves)
