"""Int8 symmetric per-block quantisation for model transfer compression.

The paper assumes "models are usually compressed before transmission"
(§IV-A, model_size = 10MB after compression).  We make compression a
first-class, kernel-backed feature: client uploads / server distribution can
be quantised to int8 with one fp32 scale per QBLOCK values (4.03 bits/value
of overhead at QBLOCK=128... 0.25 extra bytes per 128), cutting uplink bytes
~3.97x vs f32.  Both directions run as single-pass Pallas kernels.

Two granularities are exposed:

* ``quantize`` / ``dequantize`` — one flat [N] vector per call (the
  per-leaf reference path: 2 dispatches per pytree leaf);
* ``quantize_packed`` / ``dequantize_packed`` — a whole packed [m, N]
  upload buffer in ONE grid dispatch, each client row block-quantised
  independently (fleets batch it under ``jax.vmap``).  This is the
  wire format of the compressed fast path: the simulated uplink carries
  the int8 buffer plus the [m, N/QBLOCK] f32 scale rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.backend import fit_tile, padded_rows

QBLOCK = 128
DEFAULT_TILE = 2048  # values per program instance; must be multiple of QBLOCK

#: Static alias inventory (see ``safa_aggregate.ALIAS_CONTRACTS`` for the
#: format): the quantisation kernels change width/dtype between input and
#: output, so none of them can — or do — alias.  ``repro.analysis`` holds
#: the lowered cells to exactly this (JAX003/REP005); a pallas kernel
#: added here without an entry fails the inventory check.
ALIAS_CONTRACTS = {
    '_quant_kernel': ((),),
    '_dequant_kernel': ((),),
    '_quant_packed_kernel': ((),),
    '_dequant_packed_kernel': ((),),
}


# Scale layout inside the kernels.  A grid step over a ``tile``-wide
# column block owns ``tile // QBLOCK`` scales per row, fewer than the 128
# lanes a TPU block must span, so the kernels see the scales as
# ``[N // tile, rows, tile // QBLOCK]`` with the leading axis squeezed out
# of each block (the last two block dims are then the array's own).  The
# public layout stays ``[rows, N // QBLOCK]``; these two helpers convert.

def scales_to_blocks(scales, tile: int):
    """[rows, N/QBLOCK] -> [N/tile, rows, tile/QBLOCK]."""
    rows, nb = scales.shape
    sb = tile // QBLOCK
    return scales.reshape(rows, nb // sb, sb).transpose(1, 0, 2)


def scales_from_blocks(blocks):
    """Inverse of ``scales_to_blocks``."""
    nt, rows, sb = blocks.shape
    return blocks.transpose(1, 0, 2).reshape(rows, nt * sb)


def scale_spec(rows: int, tile: int, index_map):
    """BlockSpec of the blocked scale layout: ``index_map`` returns the
    (tile, row-block) indices of the step."""
    return pl.BlockSpec((None, rows, tile // QBLOCK),
                        lambda *g: (*index_map(*g), 0))


def _quant_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)              # [1, T]
    xb = x.reshape(-1, QBLOCK)                      # [T/QB, QB]
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q.reshape(1, -1)
    scale_ref[...] = scale.reshape(1, -1)


def _dequant_kernel(q_ref, scale_ref, x_ref):
    q = q_ref[...].astype(jnp.float32).reshape(-1, QBLOCK)
    scale = scale_ref[...].reshape(-1, 1)
    x_ref[...] = (q * scale).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=('tile',))
def quantize(x, *, tile: int = DEFAULT_TILE):
    """x: [N] float -> (q [N] int8, scales [N/QBLOCK] f32).  N padded
    internally to a tile multiple."""
    n = x.shape[0]
    pad = (-n) % tile
    xp = jnp.pad(x, (0, pad)).reshape(1, -1)
    np_ = xp.shape[1]
    nt = np_ // tile
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                   scale_spec(1, tile, lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.int8),
                   jax.ShapeDtypeStruct((nt, 1, tile // QBLOCK),
                                        jnp.float32)],
        interpret=backend.interpret(),
    )(xp)
    n_scales = -(-n // QBLOCK)
    return q[0, :n], s.reshape(-1)[:n_scales]


@functools.partial(jax.jit, static_argnames=('tile', 'n'))
def dequantize(q, scales, *, n: int, tile: int = DEFAULT_TILE):
    """Inverse of ``quantize``; ``n`` = original length."""
    pad = (-n) % tile
    qp = jnp.pad(q, (0, pad)).reshape(1, -1)
    np_ = qp.shape[1]
    sp = jnp.pad(scales, (0, np_ // QBLOCK - scales.shape[0]),
                 constant_values=1.0).reshape(1, -1)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(np_ // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                  scale_spec(1, tile, lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        interpret=backend.interpret(),
    )(qp, scales_to_blocks(sp, tile))
    return x[0, :n]


# ---------------------------------------------------------------------------
# Packed wire format: whole [m, N] upload buffer, one dispatch
# ---------------------------------------------------------------------------

def _quant_packed_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)              # [m, T]
    m, t = x.shape
    xb = x.reshape(m, t // QBLOCK, QBLOCK)
    amax = jnp.max(jnp.abs(xb), axis=2, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q.reshape(m, t)
    scale_ref[...] = scale.reshape(m, -1)


def _dequant_packed_kernel(q_ref, scale_ref, x_ref):
    m, t = x_ref.shape
    q = q_ref[...].astype(jnp.float32).reshape(m, t // QBLOCK, QBLOCK)
    x_ref[...] = (q * scale_ref[...][:, :, None]).reshape(m, t)



def _col_bytes(m: int) -> int:
    """VMEM bytes per lane column of the packed (de)quantise blocks: the
    f32 and int8 [m, tile] blocks, rows padded to their tiles."""
    return 4 * padded_rows(m, 4) + padded_rows(m, 1)


def _check_packed(n: int, tile: int):
    if tile % QBLOCK:
        raise ValueError(f'tile={tile} not a multiple of QBLOCK={QBLOCK}')
    if n % tile:
        raise ValueError(
            f'packed buffer width {n} not a multiple of tile={tile}; pack '
            f'with pad_to=tile (see ops.pack_spec)')


@functools.partial(jax.jit, static_argnames=('tile',))
def quantize_packed(x, *, tile: int = DEFAULT_TILE):
    """Block-quantise a whole packed upload buffer in ONE grid dispatch.

    x: [m, N] f32 pack buffer (N % tile == 0; see ``ops.pack_spec``) ->
    (q [m, N] int8, scales [m, N/QBLOCK] f32).  Each client row is
    quantised independently — exactly what m per-client ``quantize``
    calls on QBLOCK-aligned leaves produce, in 1 dispatch instead of
    2 per leaf per client.
    """
    m, n = x.shape
    _check_packed(n, tile)
    tile, params = fit_tile(tile, _col_bytes(m))
    q, s = pl.pallas_call(
        _quant_packed_kernel,
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((m, tile), lambda i: (0, i)),
                   scale_spec(m, tile, lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.int8),
                   jax.ShapeDtypeStruct((n // tile, m, tile // QBLOCK),
                                        jnp.float32)],
        compiler_params=params,
        interpret=backend.interpret(),
    )(x)
    return q, scales_from_blocks(s)


@functools.partial(jax.jit, static_argnames=('tile',))
def dequantize_packed(q, scales, *, tile: int = DEFAULT_TILE):
    """Inverse of ``quantize_packed``: (q [m, N], scales [m, N/QBLOCK]) ->
    x [m, N] f32, one grid dispatch."""
    m, n = q.shape
    _check_packed(n, tile)
    tile, params = fit_tile(tile, _col_bytes(m))
    return pl.pallas_call(
        _dequant_packed_kernel,
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i)),
                  scale_spec(m, tile, lambda i: (i, 0))],
        out_specs=pl.BlockSpec((m, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=params,
        interpret=backend.interpret(),
    )(q, scales_to_blocks(scales, tile))

