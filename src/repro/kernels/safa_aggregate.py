"""Fused SAFA discriminative aggregation (Eq. 6 + 7 + 8) as a Pallas TPU
kernel.

The server-side aggregation path is memory-bound: the naive three-step
composition reads the m cache entries three times (pre-update, weighted
reduce, post-update) and materialises two intermediate cache copies in HBM.
The fused kernel performs all three steps in one pass over parameter tiles
held in VMEM: per tile it reads cache/trained once, applies the Eq. 6 masks,
accumulates the Eq. 7 weighted sum, applies the Eq. 8 bypass write, and
emits the new global tile + new cache tile.  HBM traffic drops from
~5 model-sized reads + 3 writes to 2 reads + 2 writes (counted from the
shapes; the tier-rows kernels record what they move in
``repro.obs.counters()``, and ``bench/metrics/`` reads the kernels'
device time and roofline share from a chip trace).

Layout: parameters are flattened to [m, N] (m = clients).  Grid is over N
tiles; each program instance sees the full clients column for its tile.
The tile is narrowed from m and the dtypes (``backend.fit_tile``) so the
double-buffered blocks fit the TPU's scoped VMEM.

Two entry points share the kernel body:

* ``safa_aggregate`` — one [m, N] matrix (the leaf-wise path pads and
  launches this once per pytree leaf);
* ``safa_aggregate_packed`` — a pre-padded [m, N] buffer holding the whole
  model (see ``ops.pack_stacked``), launched exactly once per round with
  ``input_output_aliases`` donating the cache buffer to the new-cache
  output, so the server never holds two full cache copies.

The compressed-wire fast path adds ``safa_aggregate_packed_q8``: the
trained operand arrives as the int8 wire format
(q [m, N] + per-QBLOCK f32 scales, see ``comm_quant.quantize_packed``)
and is dequantised *in-register* inside the same kernel body that applies
Eq. 6-8 — the f32 [m, N] client-update matrix is never materialised in
HBM on the aggregation input, and a fully compressed round is exactly two
dispatches (quantize + this kernel).

Fleets of S servers batch every entry point under ``jax.vmap``, which
adds a leading fleet dimension to the grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import backend
from repro.kernels.backend import (LANES, SUBLANES, VMEM_BUDGET, fit_tile,
                                   padded_rows)
from repro.kernels.comm_quant import QBLOCK, scale_spec, scales_to_blocks

DEFAULT_TILE = 2048

#: Static alias inventory: kernel body name -> the admissible
#: ``input_output_aliases`` forms its ``pallas_call`` sites declare, each
#: form a tuple of (input_index, output_index) pairs in flattened call
#: order.  ``repro.analysis`` cross-checks this dict against the call
#: sites in this module (REP005) and against the lowered jaxpr of every
#: registered engine cell (JAX003), so an alias that is dropped — or
#: silently added — fails CI.  Keep in lock-step with the pallas_call
#: sites below.  ``_kernel`` admits two forms because ``_launch`` serves
#: both the leaf-wise path (fresh cache output) and the packed path
#: (cache donated in place).
ALIAS_CONTRACTS = {
    '_kernel': ((), ((0, 1),)),          # cache -> new_cache when packed
    '_q8_kernel': (((3, 1),),),          # cache -> new_cache
    '_rows_kernel': ((),),               # rows paths scatter via ops.py
    '_q8_rows_kernel': ((),),
    '_tier_rows_kernel': (((2, 2),),),   # value buffer updated in place
    '_q8_tier_rows_kernel': (((5, 2),),),
}


def _agg_math(cache, trained, g, picked, undrafted, deprecated, w):
    """Eq. 6-8 on one [m, T] tile; returns (new_global [1, T], new_cache)."""
    # Eq. 6: pre-aggregation cache update
    c1 = jnp.where(deprecated & ~picked, g, cache)
    c1 = jnp.where(picked, trained, c1)
    # Eq. 7: weighted aggregation
    new_global = jnp.sum(c1.astype(jnp.float32) * w, axis=0,
                         keepdims=True).astype(cache.dtype)
    # Eq. 8: post-aggregation (bypass) cache update
    return new_global, jnp.where(undrafted, trained, c1)


def _kernel(cache_ref, trained_ref, global_ref, picked_ref, undrafted_ref,
            deprecated_ref, weights_ref, new_global_ref, new_cache_ref):
    new_global_ref[...], new_cache_ref[...] = _agg_math(
        cache_ref[...],                 # [m, T]
        trained_ref[...],               # [m, T]
        global_ref[...],                # [1, T]
        picked_ref[...] != 0,           # [m, 1]
        undrafted_ref[...] != 0,
        deprecated_ref[...] != 0,
        weights_ref[...])               # [m, 1] float32


def check_width(np_: int, tile: int):
    if np_ % tile:
        raise ValueError(
            f'packed buffer width {np_} not a multiple of tile={tile}; '
            f'pack with pad_to=tile')


def _launch(cache, trained, global_row, picked, undrafted, deprecated,
            weights, *, tile: int, alias_cache: bool):
    """Single fused dispatch over padded [m, N] operands (N % tile == 0)."""
    m, np_ = cache.shape
    # cache, trained and new_cache blocks, plus the global rows
    tile, params = fit_tile(tile, 3 * 4 * padded_rows(m, 4) + 2 * 4 * SUBLANES)
    col = lambda arr: arr.reshape(m, 1)
    return pl.pallas_call(
        _kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((m, tile), lambda i: (0, i)),      # cache
            pl.BlockSpec((m, tile), lambda i: (0, i)),      # trained
            pl.BlockSpec((1, tile), lambda i: (0, i)),      # global
            pl.BlockSpec((m, 1), lambda i: (0, 0)),         # picked
            pl.BlockSpec((m, 1), lambda i: (0, 0)),         # undrafted
            pl.BlockSpec((m, 1), lambda i: (0, 0)),         # deprecated
            pl.BlockSpec((m, 1), lambda i: (0, 0)),         # weights
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), cache.dtype),
            jax.ShapeDtypeStruct((m, np_), cache.dtype),
        ],
        # the cache buffer is dead after the call: write new_cache in place
        input_output_aliases={0: 1} if alias_cache else {},
        compiler_params=params,
        interpret=backend.interpret(),
    )(cache, trained, global_row, col(picked.astype(jnp.int32)),
      col(undrafted.astype(jnp.int32)), col(deprecated.astype(jnp.int32)),
      col(weights.astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate(cache, trained, global_prev, picked, undrafted, deprecated,
                   weights, *, tile: int = DEFAULT_TILE):
    """cache/trained: [m, N]; global_prev: [N]; masks: [m] bool;
    weights: [m] f32.  Returns (new_global [N], new_cache [m, N])."""
    m, n = cache.shape
    pad = (-n) % tile
    if pad:
        cache = jnp.pad(cache, ((0, 0), (0, pad)))
        trained = jnp.pad(trained, ((0, 0), (0, pad)))
        global_prev = jnp.pad(global_prev, (0, pad))
    new_global, new_cache = _launch(
        cache, trained, global_prev.reshape(1, -1), picked, undrafted,
        deprecated, weights, tile=tile, alias_cache=False)
    return new_global[0, :n], new_cache[:, :n]


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed(cache, trained, global_prev, picked, undrafted,
                          deprecated, weights, *, tile: int = DEFAULT_TILE):
    """Whole-model variant: operands are pre-padded pack buffers
    (cache/trained: [m, N], global_prev: [N], N % tile == 0; see
    ``ops.pack_stacked``).  One kernel dispatch regardless of how many
    pytree leaves the model has; the cache input is aliased to the
    new-cache output.  Returns (new_global [N], new_cache [m, N])."""
    check_width(cache.shape[1], tile)
    new_global, new_cache = _launch(
        cache, trained, global_prev.reshape(1, -1), picked, undrafted,
        deprecated, weights, tile=tile, alias_cache=True)
    return new_global[0], new_cache


# ---------------------------------------------------------------------------
# Compressed-wire fast path: fused int8 dequant -> Eq. 6-8
# ---------------------------------------------------------------------------

def _dequant(q, scales):
    """int8 rows [r, T] times their per-QBLOCK scales [r, T/QBLOCK]."""
    r, t = q.shape
    return (q.astype(jnp.float32).reshape(r, t // QBLOCK, QBLOCK)
            * scales[:, :, None]).reshape(r, t)


def _q8_math(q, scales, base, cache, global_row, picked, undrafted,
             deprecated, completed, weights):
    """Dequantise the int8 client rows in-register, substitute the base
    model for crashed clients (they upload nothing), then the shared
    Eq. 6-8 body.  Returns (new_global [1, T], new_cache, new_local):
    new_local is the post-wire trained matrix (base where crashed) — the
    clients' own view of the round, emitted so the caller never needs a
    separate dequantise dispatch."""
    trained = jnp.where(completed, _dequant(q, scales), base)
    ng, nc = _agg_math(cache, trained, global_row, picked, undrafted,
                       deprecated, weights)
    return ng, nc, trained


def _q8_kernel(q_ref, scale_ref, base_ref, cache_ref, global_ref, picked_ref,
               undrafted_ref, deprecated_ref, completed_ref, weights_ref,
               new_global_ref, new_cache_ref, new_local_ref):
    new_global_ref[...], new_cache_ref[...], new_local_ref[...] = _q8_math(
        q_ref[...],                     # [m, T] int8
        scale_ref[...],                 # [m, T/QBLOCK] f32
        base_ref[...],                  # [m, T]
        cache_ref[...],                 # [m, T]
        global_ref[...],                # [1, T]
        picked_ref[...] != 0,           # [m, 1]
        undrafted_ref[...] != 0,
        deprecated_ref[...] != 0,
        completed_ref[...] != 0,
        weights_ref[...])               # [m, 1] float32


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed_q8(q, scales, base, cache, global_prev, picked,
                             undrafted, deprecated, completed, weights, *,
                             tile: int = DEFAULT_TILE):
    """Fused int8-wire Eq. 6-8: dequantise + aggregate in ONE dispatch.

    q: [m, N] int8 wire buffer; scales: [m, N/QBLOCK] f32 (both from
    ``comm_quant.quantize_packed`` on a QBLOCK-aligned pack — see
    ``ops.pack_spec(align=QBLOCK)``); base/cache: [m, N] f32 pack buffers
    (N % tile == 0); global_prev: [N]; picked/undrafted/deprecated/
    completed: [m] bool; weights: [m] f32.

    The kernel body dequantises each client tile in-register, replaces
    crashed clients' rows with their base model (no upload arrived), and
    applies the shared ``_agg_math``; the cache input is aliased to the
    new-cache output.  Returns (new_global [N], new_cache [m, N],
    new_local [m, N]) — new_local is the dequantised trained matrix with
    base rows for crashed clients, i.e. what every client locally holds
    after the round.
    """
    m, np_ = cache.shape
    check_width(np_, tile)
    # int8 q block; base, cache, new_cache and new_local f32 blocks
    tile, params = fit_tile(tile, padded_rows(m, 1)
                            + 4 * 4 * padded_rows(m, 4) + 2 * 4 * SUBLANES)
    col = lambda arr: arr.reshape(m, 1)
    new_global, new_cache, new_local = pl.pallas_call(
        _q8_kernel,
        grid=(np_ // tile,),
        in_specs=[
            pl.BlockSpec((m, tile), lambda i: (0, i)),              # q
            scale_spec(m, tile, lambda i: (i, 0)),                  # scales
            pl.BlockSpec((m, tile), lambda i: (0, i)),              # base
            pl.BlockSpec((m, tile), lambda i: (0, i)),              # cache
            pl.BlockSpec((1, tile), lambda i: (0, i)),              # global
            pl.BlockSpec((m, 1), lambda i: (0, 0)),                 # picked
            pl.BlockSpec((m, 1), lambda i: (0, 0)),                 # undrafted
            pl.BlockSpec((m, 1), lambda i: (0, 0)),                 # deprecated
            pl.BlockSpec((m, 1), lambda i: (0, 0)),                 # completed
            pl.BlockSpec((m, 1), lambda i: (0, 0)),                 # weights
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), cache.dtype),
            jax.ShapeDtypeStruct((m, np_), cache.dtype),
            jax.ShapeDtypeStruct((m, np_), cache.dtype),
        ],
        # the cache buffer is dead after the call: write new_cache in place
        input_output_aliases={3: 1},
        compiler_params=params,
        interpret=backend.interpret(),
    )(q, scales_to_blocks(scales, tile), base, cache,
      global_prev.reshape(1, -1),
      col(picked.astype(jnp.int32)), col(undrafted.astype(jnp.int32)),
      col(deprecated.astype(jnp.int32)), col(completed.astype(jnp.int32)),
      col(weights.astype(jnp.float32)))
    return new_global[0], new_cache, new_local


# ---------------------------------------------------------------------------
# Sparse active-set path: rows-indexed Eq. 6-8 deltas
# ---------------------------------------------------------------------------
#
# At production scale only K = O(quota) of the m cache rows change per
# round.  The rows kernels take the active rows' indices as a *scalar-
# prefetched* operand (pltpu.PrefetchScalarGridSpec): the grid runs over
# (N // tile, K) with the slot dim innermost, each program instance
# gathers its cache row via the index map ``rows[k]`` — only [K, N] of the
# [m, N] cache ever streams through the kernel — and the Eq. 7 aggregate
# is maintained as a *delta* on the carried running sum
# ``agg = sum_k w_k cache_k``:
#
#     new_global = agg + sum_k w_k (c1_k - cache_k)     (Eq. 6+7)
#     new_agg    = new_global + sum_k w_k (c2_k - c1_k) (Eq. 8)
#
# The new-global/new-agg output blocks are revisited across the inner k
# iterations (initialised from agg at k == 0, accumulated after), which is
# the TPU-friendly consecutive-revisit pattern.  Sentinel slots point at
# the scratch row of an [m+1, N] buffer (see ``ops.gather_rows``) and
# carry zero weight, so padding is numerically inert.
#
# A TPU block spans whole 8-row tiles, so a row is moved as the 8-row
# group that holds it (``group_spec``) and picked out of the block by a
# dynamic sublane index (``row_of``); consecutive slots share their group
# blocks, which stay resident across those steps.


def group_spec(tile: int, row):
    """BlockSpec of the 8-row group holding row ``row(j, *prefetch)`` of
    an [R, N] operand, at column tile i of a (N // tile, K) grid."""
    return pl.BlockSpec((SUBLANES, tile),
                        lambda i, j, *pf: (row(j, *pf) // SUBLANES, i))


def slot_spec(tile: int):
    """``group_spec`` of slot j's own row of a [K, N] operand."""
    return group_spec(tile, lambda j, *pf: j)


def row_of(ref, r):
    """Row ``r`` of the buffer, read from its 8-row group block."""
    return ref[pl.ds(r % SUBLANES, 1), :]


def set_row(ref, r, value):
    """Write row ``r`` into its 8-row group block."""
    ref[pl.ds(r % SUBLANES, 1), :] = value


_ROLE_SPEC = pl.BlockSpec((SUBLANES, 1),
                          lambda i, j, *pf: (j // SUBLANES, 0))


def _roles(j, picked_ref, undrafted_ref, deprecated_ref, weights_ref):
    """Slot j's (picked, undrafted, deprecated, weight), each [1, 1]."""
    return (row_of(picked_ref, j) != 0, row_of(undrafted_ref, j) != 0,
            row_of(deprecated_ref, j) != 0,
            row_of(weights_ref, j).astype(jnp.float32))


def _row_math(c0, tr, g, p, u, d):
    """Eq. 6 then Eq. 8 on one row; returns (c1, c2)."""
    c1 = jnp.where(d & ~p, g, c0)               # Eq. 6
    c1 = jnp.where(p, tr, c1)
    return c1, jnp.where(u, tr, c1)             # Eq. 8


def _accumulate(k, agg_ref, new_global_ref, new_agg_ref, w, c0, c1, c2):
    """Eq. 7 as a delta on the running sum, over the inner slot steps."""
    @pl.when(k == 0)
    def _():
        new_global_ref[...] = agg_ref[...]
        new_agg_ref[...] = agg_ref[...]

    new_global_ref[...] += w * (c1 - c0)
    new_agg_ref[...] += w * (c2 - c0)


def _rows_kernel(rows_ref, cache_ref, trained_ref, global_ref, agg_ref,
                 picked_ref, undrafted_ref, deprecated_ref, weights_ref,
                 new_global_ref, new_agg_ref, c2_ref):
    k = pl.program_id(1)
    p, u, d, w = _roles(k, picked_ref, undrafted_ref, deprecated_ref,
                        weights_ref)
    c0 = row_of(cache_ref, rows_ref[k]).astype(jnp.float32)   # [1, T]
    tr = row_of(trained_ref, k).astype(jnp.float32)
    c1, c2 = _row_math(c0, tr, global_ref[...].astype(jnp.float32), p, u, d)
    set_row(c2_ref, k, c2.astype(c2_ref.dtype))
    _accumulate(k, agg_ref, new_global_ref, new_agg_ref, w, c0, c1, c2)


def _row_args(agg, global_prev, masks, w_rows):
    """The per-round operands of a rows call after its row operands: the
    [1, N] global and agg rows, then one [K, 1] column per role mask and
    the weights column."""
    col = lambda arr, dt: arr.astype(dt).reshape(-1, 1)
    return (global_prev.reshape(1, -1).astype(jnp.float32),
            agg.reshape(1, -1).astype(jnp.float32),
            *(col(mask, jnp.int32) for mask in masks),
            col(w_rows, jnp.float32))


def _row_specs(tile: int, n_masks: int):
    """Specs of ``_row_args``."""
    full = pl.BlockSpec((1, tile), lambda i, j, *pf: (0, i))
    return [full, full] + [_ROLE_SPEC] * (n_masks + 1)


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed_rows(cache, trained_rows, global_prev, agg, rows,
                               picked_r, undrafted_r, deprecated_r, w_rows,
                               *, tile: int = DEFAULT_TILE):
    """Rows-indexed Eq. 6-8: one dispatch touching only the K active rows.

    cache: [R, N] pack buffer (R = m, or m+1 with a trailing scratch row
    when ``rows`` uses the sentinel index m); trained_rows: [K, N] (the
    committed rows' post-wire uploads, base rows elsewhere); global_prev,
    agg: [N] (agg = the running Eq. 7 sum, f32); rows: [K] int32 < R;
    picked_r/undrafted_r/deprecated_r: [K] bool per-slot roles; w_rows:
    [K] f32 aggregation weights (0 at padding slots).

    Returns (new_global [N] f32, new_agg [N] f32, c2_rows [K, N]) — the
    caller scatters ``c2_rows`` back with ``ops.scatter_rows`` (the
    untouched cache rows are untouched by construction).
    """
    _, np_ = cache.shape
    k, _ = trained_rows.shape
    check_width(np_, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // tile, k),      # k innermost: agg blocks revisit
        in_specs=[
            group_spec(tile, lambda j, rows: rows[j]),      # cache
            slot_spec(tile),                                # trained
            *_row_specs(tile, 3),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i, j, rows: (0, i)),
            pl.BlockSpec((1, tile), lambda i, j, rows: (0, i)),
            slot_spec(tile),
        ])
    new_global, new_agg, c2 = pl.pallas_call(
        _rows_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((k, np_), cache.dtype),
        ],
        interpret=backend.interpret(),
    )(rows.astype(jnp.int32), cache, trained_rows,
      *_row_args(agg, global_prev, (picked_r, undrafted_r, deprecated_r),
                 w_rows))
    return new_global[0], new_agg[0], c2


def _int8_row(ref, r):
    """Row r of an int8 group block, as f32.  int8 rows pack four to a
    sublane, so the row is selected arithmetically (exact: the values
    are small integers) rather than by a sublane index."""
    x = ref[...].astype(jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(sub == r % SUBLANES, x, 0.0), axis=0,
                   keepdims=True)


def _q8_row(k, q_ref, scale_ref, base_ref, completed):
    """Slot k's post-wire upload: its dequantised int8 row, or its base
    row when it crashed (``completed``, [1, 1], False)."""
    deq = _dequant(_int8_row(q_ref, k), row_of(scale_ref, k))
    return jnp.where(completed, deq, row_of(base_ref, k).astype(jnp.float32))


def _q8_rows_kernel(rows_ref, q_ref, scale_ref, base_ref, cache_ref,
                    global_ref, agg_ref, picked_ref, undrafted_ref,
                    deprecated_ref, completed_ref, weights_ref,
                    new_global_ref, new_agg_ref, c2_ref, local_ref):
    k = pl.program_id(1)
    tr = _q8_row(k, q_ref, scale_ref, base_ref, row_of(completed_ref, k) != 0)
    set_row(local_ref, k, tr.astype(local_ref.dtype))
    p, u, d, w = _roles(k, picked_ref, undrafted_ref, deprecated_ref,
                        weights_ref)
    c0 = row_of(cache_ref, rows_ref[k]).astype(jnp.float32)
    c1, c2 = _row_math(c0, tr, global_ref[...].astype(jnp.float32), p, u, d)
    set_row(c2_ref, k, c2.astype(c2_ref.dtype))
    _accumulate(k, agg_ref, new_global_ref, new_agg_ref, w, c0, c1, c2)


def _q8_slot_specs(tile: int):
    """Specs of a rows call's wire operands: q, scales, base rows."""
    return [slot_spec(tile),
            scale_spec(SUBLANES, tile,
                       lambda i, j, *pf: (i, j // SUBLANES)),
            slot_spec(tile)]


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed_q8_rows(q_rows, scales_rows, base_rows, cache,
                                  global_prev, agg, rows, picked_r,
                                  undrafted_r, deprecated_r, completed_r,
                                  w_rows, *, tile: int = DEFAULT_TILE):
    """int8-wire variant of ``safa_aggregate_packed_rows``: the K active
    rows' uploads arrive as the wire format (q_rows [K, N] int8 +
    scales_rows [K, N/QBLOCK] f32) and are dequantised in-register;
    crashed slots (completed_r False) fall back to base_rows.  Returns
    (new_global [N] f32, new_agg [N] f32, c2_rows [K, N], local_rows
    [K, N]) — local_rows is each active client's post-round local model,
    for the caller to scatter into the local stack."""
    _, np_ = cache.shape
    k, _ = q_rows.shape
    check_width(np_, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // tile, k),
        in_specs=[
            *_q8_slot_specs(tile),
            group_spec(tile, lambda j, rows: rows[j]),      # cache
            *_row_specs(tile, 4),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i, j, rows: (0, i)),
            pl.BlockSpec((1, tile), lambda i, j, rows: (0, i)),
            slot_spec(tile),
            slot_spec(tile),
        ])
    new_global, new_agg, c2, local = pl.pallas_call(
        _q8_rows_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((k, np_), cache.dtype),
            jax.ShapeDtypeStruct((k, np_), cache.dtype),
        ],
        interpret=backend.interpret(),
    )(rows.astype(jnp.int32), q_rows, scales_to_blocks(scales_rows, tile),
      base_rows, cache,
      *_row_args(agg, global_prev,
                 (picked_r, undrafted_r, deprecated_r, completed_r), w_rows))
    return new_global[0], new_agg[0], c2, local


# ---------------------------------------------------------------------------
# Lag-tier path: slot-indirected rows kernels over the tier value buffer
# ---------------------------------------------------------------------------
#
# The tier engines (protocol.safa_round_sparse_tier_packed) carry one
# value buffer of capacity+1 rows instead of [m, N] local/cache stacks;
# the host schedule names each slot's cache-read slot (``srcs``) and
# cache-write slot (``dsts``), both scalar-prefetched.  The kernels below
# are the rows kernels with TWO prefetch operands and the c2 scatter
# folded in: each step writes its c2 row straight into the buffer at row
# dsts[j], and the buffer input is aliased to the output, so one dispatch
# does Eq. 6-8, both delta sums, AND the cache write-back in place.  Sound
# because the host allocator guarantees per-round src/dst slot
# disjointness (a value written in round t is first read strictly later);
# the shared scratch slot (read AND written by inert slots) carries only
# zero-weight contributions, so its value never matters.  Dst-duplicate
# scratch writes resolve last-wins over the innermost grid dim, exactly
# like ``ops.scatter_rows``.
#
# The buffer is a row slab [R, N/128, 128]: each row is a run of whole
# (8, 128) tiles, so the window of one row's column tile is itself whole
# tiles, and a TPU DMA moves exactly that row (an [R, N] buffer's tiles
# span 8 rows, so a copy of one row moves its whole 8-row group).  Each
# step copies its source row's window into a (T/128, 128) VMEM scratch
# row and copies c2 from that scratch out to the destination row: two
# DMAs of one f32 row each.  The row math runs in the same dense
# (T/128, 128) form, as do the global and agg rows; only the slot's
# upload, read from its 8-row group block, is reshaped.  The copies of a
# step are started and waited on one after another, so their latency is
# paid once per column tile and slot: the column tile is the widest that
# divides the packed width and fits VMEM (``tier_tile``), which moves the
# same bytes in the fewest copies, and every column's sums over the slots
# run in the same order at any width and in either form.


def _group_window(hbm, r, i, tile: int):
    """HBM window of the 8-row group holding row r, column tile i."""
    g = pl.multiple_of(r // SUBLANES * SUBLANES, SUBLANES)
    return hbm.at[pl.ds(g, SUBLANES), pl.ds(pl.multiple_of(i * tile, tile),
                                            tile)]


def _copy(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def write_row(hbm, grp, sem, r, i, tile: int, value):
    """Write ``value`` to row r, column tile i of an [R, N] HBM buffer by
    a read-modify-write of its 8-row group through VMEM scratch grp."""
    window = _group_window(hbm, r, i, tile)
    _copy(window, grp, sem)
    set_row(grp, r, value)
    _copy(grp, window, sem)


def check_rows(r: int):
    """Buffers written in place by row hold whole 8-row groups."""
    if r % SUBLANES:
        raise ValueError(
            f'a buffer written in place by row needs a multiple of '
            f'{SUBLANES} rows, got {r}; allocate backend.row_pad(rows)')


def slab_width(shape) -> int:
    """Packed width N of a row slab of ``shape`` [R, N/128, 128]; raises
    unless each row is a run of whole (8, 128) tiles."""
    if len(shape) != 3 or shape[2] != LANES or shape[1] % SUBLANES:
        raise ValueError(
            f'a row slab is [R, N/{LANES}, {LANES}] with N/{LANES} a '
            f'multiple of {SUBLANES}, so that each row is whole '
            f'({SUBLANES}, {LANES}) tiles; got {tuple(shape)}')
    return shape[1] * LANES


def as_slab(rows):
    """Rows [..., N] laid out as the row slab [..., N/128, 128]."""
    return rows.reshape(rows.shape[:-1] + (-1, LANES))


def check_slab_tile(n: int, tile: int):
    """A slab column tile divides the width and is whole (8, 128) tiles."""
    check_width(n, tile)
    if tile % (SUBLANES * LANES):
        raise ValueError(
            f'a row slab moves whole ({SUBLANES}, {LANES}) tiles: tile '
            f'{tile} is not a multiple of {SUBLANES * LANES}')


def _slab_window(hbm, r, i, rows: int):
    """HBM window of row r of a slab buffer at column tile i, ``rows``
    (8, 128)-tile rows of 128 lanes each."""
    return hbm.at[r, pl.ds(pl.multiple_of(i * rows, rows), rows), :]


def read_slab_row(hbm, row, sem, r, i):
    """Row r, column tile i of a slab buffer in HBM, copied into the VMEM
    scratch ``row`` ((T/128, 128)) by one DMA; returns its value."""
    _copy(_slab_window(hbm, r, i, row.shape[0]), row, sem)
    return row[...]


def write_slab_row(hbm, row, sem, r, i, value):
    """Write ``value`` ((T/128, 128)) to row r, column tile i of a slab
    buffer in HBM through the VMEM scratch ``row``, by one DMA."""
    row[...] = value
    _copy(row, _slab_window(hbm, r, i, row.shape[0]), sem)


def _tier_step(srcs_ref, dsts_ref, tr, global_ref, agg_ref, role_refs,
               new_global_ref, new_agg_ref, newbuf_ref, row, sem):
    """One (column tile, slot) step of a tier-rows kernel, given the
    slot's post-wire upload ``tr`` as a (1, T) row: read the cache row at
    srcs[k], Eq. 6 and 8, write c2 to dsts[k], and the Eq. 7 deltas."""
    i, k = pl.program_id(0), pl.program_id(1)
    p, u, d, w = _tier_roles(k, *role_refs)
    c0 = read_slab_row(newbuf_ref, row, sem, srcs_ref[k],
                       i).astype(jnp.float32)
    c1, c2 = _row_math(c0, tr.reshape(c0.shape),
                       global_ref[...].astype(jnp.float32), p, u, d)
    write_slab_row(newbuf_ref, row, sem, dsts_ref[k], i,
                   c2.astype(row.dtype))
    _accumulate(k, agg_ref, new_global_ref, new_agg_ref, w, c0, c1, c2)


def _tier_rows_kernel(srcs_ref, dsts_ref, buf_ref, trained_ref, global_ref,
                      agg_ref, picked_ref, undrafted_ref, deprecated_ref,
                      weights_ref, new_global_ref, new_agg_ref, newbuf_ref,
                      row, sem):
    # buf_ref aliases newbuf_ref: every read and write goes through the
    # output, which holds the buffer's current contents
    del buf_ref
    tr = row_of(trained_ref, pl.program_id(1)).astype(jnp.float32)
    _tier_step(srcs_ref, dsts_ref, tr, global_ref, agg_ref,
               (picked_ref, undrafted_ref, deprecated_ref, weights_ref),
               new_global_ref, new_agg_ref, newbuf_ref, row, sem)


#: explicit DMAs of one tier-rows grid step: ``read_slab_row`` copies the
#: source row in, ``write_slab_row`` copies c2 out to the destination row
TIER_STEP_DMAS = 2


def pipelined_bytes(grid, specs, arrays) -> int:
    """Bytes the grid pipeline copies for the blocked operands ``arrays``
    of ``specs``: a block moves whenever its index differs from the one
    of the step before (steps in grid order, the last axis fastest).
    Operands left in HBM (``pl.ANY``) move nothing here."""
    steps = np.indices(grid).reshape(len(grid), -1)
    total = 0
    for spec, arr in zip(specs, arrays):
        if spec.block_shape is None:
            continue
        idx = np.stack(np.broadcast_arrays(
            steps[0], *spec.index_map(*steps))[1:])
        moves = 1 + int(np.count_nonzero(
            np.any(idx[:, 1:] != idx[:, :-1], axis=0)))
        block = math.prod(1 if d is None else d for d in spec.block_shape)
        total += moves * block * jnp.dtype(arr.dtype).itemsize
    return total


def tier_tile(n: int, col_bytes: int):
    """Column tile and compiler params of a tier-rows call over packed
    width n whose grid steps hold twice ``col_bytes`` bytes of VMEM per
    lane column (``backend.fit_tile``'s measure).  The widest multiple
    of the pack granule ``DEFAULT_TILE`` that divides n and fits
    ``backend.VMEM_BUDGET``: a step's fixed cost and its DMAs' latency
    are paid once per column tile and slot, its bytes are not, so the
    widest tile moves the same bytes in the fewest steps and copies.
    Where no granule multiple fits, ``fit_tile`` narrows the granule."""
    check_width(n, DEFAULT_TILE)
    fits = [t for t in range(DEFAULT_TILE, n + 1, DEFAULT_TILE)
            if n % t == 0 and 2 * col_bytes * t <= VMEM_BUDGET]
    return fit_tile(max(fits, default=DEFAULT_TILE), col_bytes)


#: VMEM a tier-rows grid step needs, in bytes a lane column of its tile
#: (its blocks, the scratch row and the body's temporaries), by wire: the
#: least scoped-VMEM limit at which a v5e compile of the call at 251
#: slots takes tiles of 38,912 and 116,736 lanes.  A chip's compiler has
#: asked ~11% more, which ``VMEM_BUDGET``'s headroom covers
TIER_VMEM_BYTES = {'f32': 98, 'int8': 137}


def _tier_width(n: int, tile, wire: str):
    """``(tile, None)`` for a tile given, else ``tier_tile``'s choice for
    width n, whose steps ask ``TIER_VMEM_BYTES[wire]`` a lane column."""
    if tile is not None:
        return tile, None
    return tier_tile(n, TIER_VMEM_BYTES[wire] // 2)


def _dense_spec(tile: int):
    """Block of column tile i of a row held dense as [N/128, 128]."""
    return pl.BlockSpec((tile // LANES, LANES), lambda i, j, *pf: (i, 0))


def _tier_args(agg, global_prev, masks, w_rows):
    """``_row_args`` with the global and agg rows dense, [N/128, 128],
    and each role and weight column as wide as a lane row, [K, 128]."""
    g, a, *cols = _row_args(agg, global_prev, masks, w_rows)
    return (g.reshape(-1, LANES), a.reshape(-1, LANES),
            *(jnp.broadcast_to(c, (c.shape[0], LANES)) for c in cols))


def _tier_row_specs(tile: int, k: int, n_masks: int):
    """Specs of ``_tier_args``: the role and weight rows stay resident."""
    roles = pl.BlockSpec((k, LANES), lambda i, j, *pf: (0, 0))
    return [_dense_spec(tile)] * 2 + [roles] * (n_masks + 1)


def _tier_roles(k, picked_ref, undrafted_ref, deprecated_ref, weights_ref):
    """Slot k's (picked, undrafted, deprecated, weight), each [1, 128]:
    a value a dense (T/128, 128) row takes by a sublane broadcast (a
    [1, 1] one would need a broadcast in sublanes and lanes at once,
    which the TPU compiler does not lower)."""
    row = lambda ref: ref[pl.ds(k, 1), :]
    return (row(picked_ref) != 0, row(undrafted_ref) != 0,
            row(deprecated_ref) != 0, row(weights_ref).astype(jnp.float32))


def _tier_grid(name, in_specs, operands, buf, *, k: int, tile: int):
    """Grid spec and output shapes of a tier-rows dispatch over the slab
    buf [R, N/128, 128]: new_global and new_agg rows (dense, [N/128,
    128]), then the buffer itself, which stays in HBM and is written in
    place.  ``operands`` are the call's arrays after its two prefetched
    slot-id vectors, one per spec of ``in_specs``.  Records under
    ``name`` in ``obs.counters()`` the bytes the call's DMAs move (its
    explicit row copies and its pipelined blocks) and how many explicit
    DMAs it issues: every slot issues the same, sentinel slots included,
    so the count is exact."""
    np_ = slab_width(buf.shape)
    check_slab_tile(np_, tile)
    grid = (np_ // tile, k)         # k innermost: agg blocks revisit
    out_specs = [_dense_spec(tile), _dense_spec(tile),
                 pl.BlockSpec(memory_space=pl.ANY)]                 # new buf
    out_shape = [
        jax.ShapeDtypeStruct((np_ // LANES, LANES), jnp.float32),
        jax.ShapeDtypeStruct((np_ // LANES, LANES), jnp.float32),
        jax.ShapeDtypeStruct(buf.shape, buf.dtype),
    ]
    dmas = TIER_STEP_DMAS * math.prod(grid)
    row_bytes = tile * jnp.dtype(buf.dtype).itemsize
    obs.count(name, dmas=dmas, bytes=dmas * row_bytes
              + pipelined_bytes(grid, in_specs, operands)
              + pipelined_bytes(grid, out_specs, out_shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tile // LANES, LANES), buf.dtype),
                        pltpu.SemaphoreType.DMA])
    return grid_spec, out_shape


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed_tier_rows(buf, trained_rows, global_prev, agg,
                                    srcs, dsts, picked_r, undrafted_r,
                                    deprecated_r, w_rows, *,
                                    tile: int | None = None):
    """Slot-indirected Eq. 6-8 with the cache write-back fused in place.

    buf: [capacity+1, N/128, 128] tier value buffer, a row slab (trailing
    scratch row; N/128 a multiple of 8, see ``slab_width``);
    trained_rows: [K, N] post-wire uploads (base rows where not
    committed); global_prev, agg: [N]; srcs/dsts: [K] int32 slot ids
    (cache-read / cache-write, scratch == discard); roles/weights as in
    ``safa_aggregate_packed_rows``.  The buffer input aliases the new-
    buffer output, so untouched slots persist with zero traffic.  ``tile``
    defaults to ``tier_tile``'s choice for N.  Returns (new_global [N]
    f32, new_agg [N] f32, new_buf [capacity+1, N/128, 128])."""
    k, np_ = trained_rows.shape
    tile, params = _tier_width(np_, tile, 'f32')
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),                      # buf
        slot_spec(tile),                                        # trained
        *_tier_row_specs(tile, k, 3),
    ]
    slots = (srcs.astype(jnp.int32), dsts.astype(jnp.int32))
    operands = (buf, trained_rows,
                *_tier_args(agg, global_prev,
                            (picked_r, undrafted_r, deprecated_r), w_rows))
    grid_spec, out_shape = _tier_grid(
        'safa_aggregate_packed_tier_rows', in_specs, operands, buf, k=k,
        tile=tile)
    new_global, new_agg, new_buf = pl.pallas_call(
        _tier_rows_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operands 0/1 are the prefetched slot ids, so buf is input index
        # 2; it aliases the new-buffer output (index 2): written in place
        input_output_aliases={2: 2},
        compiler_params=params,
        interpret=backend.interpret(),
    )(*slots, *operands)
    return new_global.reshape(-1), new_agg.reshape(-1), new_buf


def _q8_tier_rows_kernel(srcs_ref, dsts_ref, q_ref, scale_ref, base_ref,
                         buf_ref, global_ref, agg_ref, picked_ref,
                         undrafted_ref, deprecated_ref, completed_ref,
                         weights_ref, new_global_ref, new_agg_ref,
                         newbuf_ref, row, sem):
    del buf_ref             # aliased: read and written through newbuf_ref
    k = pl.program_id(1)
    tr = _q8_row(k, q_ref, scale_ref, base_ref,
                 completed_ref[pl.ds(k, 1), :1] != 0)
    _tier_step(srcs_ref, dsts_ref, tr, global_ref, agg_ref,
               (picked_ref, undrafted_ref, deprecated_ref, weights_ref),
               new_global_ref, new_agg_ref, newbuf_ref, row, sem)


@functools.partial(jax.jit, static_argnames=('tile',))
def safa_aggregate_packed_q8_tier_rows(q_rows, scales_rows, base_rows, buf,
                                       global_prev, agg, srcs, dsts,
                                       picked_r, undrafted_r, deprecated_r,
                                       completed_r, w_rows, *,
                                       tile: int | None = None):
    """int8-wire variant of ``safa_aggregate_packed_tier_rows``: uploads
    arrive as the wire format and dequantise in-register; crashed slots
    fall back to base_rows.  No local output exists — tier local state is
    virtual (base rows are always version snapshots).  Returns
    (new_global [N] f32, new_agg [N] f32, new_buf [capacity+1, N/128,
    128])."""
    k, np_ = q_rows.shape
    tile, params = _tier_width(np_, tile, 'int8')
    in_specs = [
        *_q8_slot_specs(tile),
        pl.BlockSpec(memory_space=pl.ANY),                      # buf
        *_tier_row_specs(tile, k, 4),
    ]
    slots = (srcs.astype(jnp.int32), dsts.astype(jnp.int32))
    operands = (q_rows, scales_to_blocks(scales_rows, tile), base_rows, buf,
                *_tier_args(agg, global_prev,
                            (picked_r, undrafted_r, deprecated_r,
                             completed_r), w_rows))
    grid_spec, out_shape = _tier_grid(
        'safa_aggregate_packed_q8_tier_rows', in_specs, operands, buf, k=k,
        tile=tile)
    new_global, new_agg, new_buf = pl.pallas_call(
        _q8_tier_rows_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operands 0/1 are prefetched slot ids, so buf is input index 5;
        # it aliases the new-buffer output (index 2)
        input_output_aliases={5: 2},
        compiler_params=params,
        interpret=backend.interpret(),
    )(*slots, *operands)
    return new_global.reshape(-1), new_agg.reshape(-1), new_buf
