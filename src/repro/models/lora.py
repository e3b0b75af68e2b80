"""Low-rank adapters (LoRA, Hu et al., arXiv:2106.09685) on a frozen base.

A projection ``y = x W`` gains ``(ALPHA / RANK) * (x A) B`` with ``A``
[d_in, RANK] drawn normal and ``B`` [RANK, d_out] zero, so a fresh
adapter leaves the base's output as it is.  Only ``A`` and ``B`` train;
federated LoRA (FedIT, OpenFedLLM) uploads and aggregates only them.

Adapters are float32.  The base's matmul takes bfloat16 operands and
accumulates in float32; the adapter's path runs on the float32 input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

RANK = 16
ALPHA = 32.0


def init(key, shapes: dict):
    """``shapes``: ``{name: (layers, d_in, d_out)}`` -> ``{name: {'a':
    [layers, d_in, RANK], 'b': [layers, RANK, d_out]}}``, A normal with
    standard deviation 1/sqrt(d_in), B zero."""
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (layers, d_in, d_out)) in zip(keys, sorted(shapes.items())):
        out[name] = {
            'a': jax.random.normal(k, (layers, d_in, RANK), jnp.float32)
            * (d_in ** -0.5),
            'b': jnp.zeros((layers, RANK, d_out), jnp.float32),
        }
    return out


def flat(tree: dict, prefix: str = '') -> dict:
    """Nested adapters -> one dict keyed by dotted paths
    (``'mamba.in_proj.a'``): the parameter tree a federated task
    uploads, one leaf per matrix."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f'{prefix}{k}.'))
        else:
            out[prefix + k] = v
    return out


def nest(flat_tree: dict) -> dict:
    """The inverse of ``flat``."""
    out = {}
    for path, v in flat_tree.items():
        *groups, leaf = path.split('.')
        node = out
        for g in groups:
            node = node.setdefault(g, {})
        node[leaf] = v
    return out


def count(shapes: dict) -> int:
    """Adapter parameters of ``shapes`` (as ``init`` takes them)."""
    return sum(layers * RANK * (d_in + d_out)
               for layers, d_in, d_out in shapes.values())


def dense(x, w, adapter=None):
    """``x @ w`` in float32 from bfloat16 operands, plus the adapter's
    ``(ALPHA / RANK) * (x A) B`` when ``adapter`` ({'a', 'b'} of one
    layer) is given."""
    y = jnp.einsum('...d,de->...e', x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)
    if adapter is None:
        return y
    low = jnp.einsum('...d,dr->...r', x.astype(jnp.float32), adapter['a'])
    return y + (ALPHA / RANK) * jnp.einsum('...r,re->...e', low,
                                           adapter['b'])
