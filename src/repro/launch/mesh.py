"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
import; tests and benches see the real (1-device) platform.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """(data, model) mesh over every local device, with the production
    axis names: 1x1 on one device, (n/2)x2 on an even count n.  An odd
    count above one has no such layout and is refused rather than run
    on fewer devices than the host has."""
    n = len(jax.devices())
    if n == 1:
        return jax.make_mesh((1, 1), ("data", "model"))
    if n % 2:
        raise ValueError(
            f'no (data, model) mesh with a model axis of 2 over {n} '
            f'devices; run on one device or an even number of them')
    return jax.make_mesh((n // 2, 2), ("data", "model"))


def n_clients(mesh: Mesh, client_axes=("pod", "data")) -> int:
    """Silo-mode federated client count = product of client axes present."""
    c = 1
    for ax in client_axes:
        if ax in mesh.axis_names:
            c *= mesh.shape[ax]
    return c


def describe(mesh: Mesh) -> str:
    return 'x'.join(f'{k}={v}' for k, v in mesh.shape.items())
