"""Where the Pallas kernels run, and how big their blocks may be.

Kernels compile for the TPU and run through the Pallas interpreter on the
CPU (the tests' platform); any other platform is an error.  The choice is
made each time a kernel is traced, from the platform the computation is
placed on, never at import: importing a kernel module starts no backend.

Block widths are chosen here too, from the rows a block holds and their
dtypes, so that the double-buffered blocks of one grid step fit the
TPU's scoped VMEM.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

#: v5e's default scoped-VMEM limit is 16 MiB; the double-buffered blocks
#: of one grid step are held under this, leaving room for the kernel
#: body's temporaries.
VMEM_BUDGET = 12 * 2**20
#: Largest scoped-VMEM limit a kernel may ask for (v5e has 128 MiB).
VMEM_CEILING = 100 * 2**20
LANES = 128
#: Rows of one f32 VMEM/HBM tile.  Row-indirected kernels move whole
#: 8-row groups, so buffers they write in place hold a multiple of this.
SUBLANES = 8


def kernel_platform() -> str:
    """Platform of the computation being traced: the default device's
    when one is set (``jax.default_device``), else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def interpret() -> bool:
    """``interpret=`` for a ``pallas_call`` traced now: False on a TPU,
    True on a CPU, and an error anywhere else."""
    platform = kernel_platform()
    if platform == 'tpu':
        return False
    if platform == 'cpu':
        return True
    raise RuntimeError(
        f'Pallas kernels compile for TPU and are interpreted only on CPU; '
        f'this computation is placed on {platform!r}')


def row_pad(rows: int) -> int:
    """``rows`` rounded up to a whole number of 8-row tiles: the row count
    of a buffer that the row-indirected kernels write in place."""
    return -(-rows // SUBLANES) * SUBLANES


def fit_tile(tile: int, col_bytes: int):
    """Block width and compiler params for a kernel whose blocks hold
    ``col_bytes`` bytes per lane column, summed over every blocked operand
    (rows padded to the dtype's tile).  Halves ``tile`` (a power-of-two
    multiple of 128, so every smaller one still divides the packed width)
    until the double-buffered blocks fit ``VMEM_BUDGET``; past 128 lanes
    it raises the kernel's VMEM limit instead.  Returns (tile, params)."""
    while tile > LANES and tile % (2 * LANES) == 0 \
            and 2 * col_bytes * tile > VMEM_BUDGET:
        tile //= 2
    need = 2 * col_bytes * tile
    if need <= VMEM_BUDGET:
        return tile, None
    limit = need + 4 * 2**20
    if limit > VMEM_CEILING:
        raise ValueError(
            f'blocks of {col_bytes} bytes per column need {limit} bytes of '
            f'VMEM at tile={tile}, above the {VMEM_CEILING}-byte ceiling')
    return tile, pltpu.CompilerParams(vmem_limit_bytes=limit)


def padded_rows(rows: int, itemsize: int) -> int:
    """Rows a block of ``rows`` occupies in VMEM: f32 tiles hold 8 rows,
    bf16 16, int8 32."""
    per_tile = SUBLANES * (4 // itemsize)
    return -(-rows // per_tile) * per_tile
