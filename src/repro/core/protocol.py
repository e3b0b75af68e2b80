"""SAFA numeric protocol algebra (Eq. 3, 6, 7, 8) on stacked client pytrees.

Everything here is mask-driven and jit-able.  Client pytrees carry a leading
``clients`` dim of size m; in simulation mode it is a stacked replica axis,
in silo mode it is sharded over the ``("pod", "data")`` mesh axes.

The server's *cache* (one entry per client) and the *bypass* are realised as
masked updates: picked entries overwrite pre-aggregation (Eq. 6), undrafted
entries overwrite post-aggregation (Eq. 8) — bit-identical to the paper's
three-step discriminative aggregation (tests assert the step-by-step
equivalence).

Every op of a round body sits under one ``obs.scope`` phase: ``rows``
(models moved between the state and training), ``train`` (the
``local_train_fn`` call), ``wire`` (quantisation outside the aggregation
kernel) and ``aggregate`` (Eq. 6-8).  The round functions below are
shared by the scan, loop and fleet engines, so all of them carry the
scopes.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

def _bmask(mask, leaf):
    """Broadcast a [m] client mask against a [m, ...] leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def masked_select(mask, a, b):
    """Per-client where: leaf = mask ? a : b  (mask: [m] bool)."""
    return jax.tree.map(lambda x, y: jnp.where(_bmask(mask, x), x, y), a, b)


def broadcast_global(global_tree, m: int):
    """Tile the global model across the clients dim."""
    return jax.tree.map(
        lambda g: jnp.broadcast_to(g[None], (m,) + g.shape), global_tree)


# ---------------------------------------------------------------------------
# Eq. 3 — lag-tolerant distribution
# ---------------------------------------------------------------------------

def distribute(global_w, local_w, sync_mask):
    """sync_mask[k] True => client k (up-to-date or deprecated) takes the
    latest global model; tolerable clients keep their local model."""
    m = sync_mask.shape[0]
    g = broadcast_global(global_w, m)
    return masked_select(sync_mask, g, local_w)


def classify_versions(versions, global_version, lag_tolerance,
                      committed_prev=None):
    """Client states at round start.

    versions[k] = version of the base model client k currently holds.
    up-to-date:  committed last round (their base will be the new global);
    deprecated:  staleness >= lag_tolerance (Eq. 3: v < t - tau);
    tolerable:   in between.
    """
    staleness = global_version - versions
    if committed_prev is None:
        up_to_date = staleness <= 0
    else:
        up_to_date = committed_prev
    deprecated = (~up_to_date) & (staleness >= lag_tolerance)
    tolerable = (~up_to_date) & (~deprecated)
    return up_to_date, deprecated, tolerable


# ---------------------------------------------------------------------------
# Eq. 6/7/8 — three-step discriminative aggregation
# ---------------------------------------------------------------------------

class AggregationResult(NamedTuple):
    new_global: Any
    new_cache: Any


def pre_agg_cache_update(cache, trained, global_prev, picked, deprecated):
    """Eq. 6.  picked -> trained update; deprecated (and not picked) ->
    previous global; otherwise keep the existing entry."""
    m = picked.shape[0]
    g = broadcast_global(global_prev, m)
    out = masked_select(deprecated & ~picked, g, cache)
    out = masked_select(picked, trained, out)
    return out


def aggregate(cache, weights):
    """Eq. 7: w(t) = sum_k (n_k / n) * cache_k.  weights: [m], sums to 1."""
    def red(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
        return jnp.sum(leaf.astype(jnp.float32) * w, axis=0).astype(leaf.dtype)
    return jax.tree.map(red, cache)


def post_agg_cache_update(cache, trained, undrafted):
    """Eq. 8: undrafted updates enter the cache for the *next* round."""
    return masked_select(undrafted, trained, cache)


def discriminative_aggregation(cache, trained, global_prev, *, picked,
                               undrafted, deprecated, weights,
                               use_kernel=False) -> AggregationResult:
    """The full three-step aggregation.

    ``use_kernel`` routes the fused Pallas path (kernels/safa_aggregate):
    ``True`` launches the fused kernel once per pytree leaf; ``'packed'``
    flattens the model into one buffer and launches exactly once per call.
    """
    if use_kernel not in (False, True, 'packed'):
        raise ValueError(
            f'unknown use_kernel {use_kernel!r} (want False, True, or '
            f'"packed")')
    if use_kernel:
        from repro.kernels import ops as kops
        if use_kernel == 'packed':
            return kops.safa_aggregate_tree_packed(
                cache, trained, global_prev, picked=picked,
                undrafted=undrafted, deprecated=deprecated, weights=weights)
        return kops.safa_aggregate_tree(
            cache, trained, global_prev, picked=picked, undrafted=undrafted,
            deprecated=deprecated, weights=weights)
    cache1 = pre_agg_cache_update(cache, trained, global_prev, picked, deprecated)
    new_global = aggregate(cache1, weights)
    cache2 = post_agg_cache_update(cache1, trained, undrafted)
    return AggregationResult(new_global, cache2)


# ---------------------------------------------------------------------------
# One full numeric SAFA round (jit-able), generic over a local-train fn
# ---------------------------------------------------------------------------

def check_wire(wire: str):
    if wire not in ('f32', 'int8'):
        raise ValueError(f"unknown wire {wire!r} (want 'f32' or 'int8')")


def safa_server_step(base, trained, cache, global_w, *, completed, picked,
                     undrafted, deprecated, weights, use_kernel=False,
                     wire='f32'):
    """Everything the SAFA server does after local training: the wire
    transfer plus the Eq. 6-8 discriminative aggregation plus the local
    sync.  Split out of ``safa_round`` so the sparse-schedule round can
    scatter its trained rows into the dense stacks and then run the exact
    same trace — that is what makes sparse==dense a bit-identity, not an
    allclose.  Returns (new_global, new_local, new_cache)."""
    if wire == 'int8':
        from repro.kernels import ops as kops
        return kops.safa_compressed_update(
            base, trained, cache, global_w, picked=picked,
            undrafted=undrafted, deprecated=deprecated, completed=completed,
            weights=weights)
    with obs.scope('aggregate'):
        # crashed clients make no visible progress this round
        trained = masked_select(completed, trained, base)
        res = discriminative_aggregation(
            cache, trained, global_w, picked=picked, undrafted=undrafted,
            deprecated=deprecated, weights=weights, use_kernel=use_kernel)
    with obs.scope('rows'):
        # committed clients now hold their own trained model locally
        new_local = masked_select(completed, trained, base)
    return res.new_global, new_local, res.new_cache


def safa_round(global_w, local_w, cache, *, sync_mask, completed, picked,
               undrafted, deprecated, weights, local_train_fn, train_args=(),
               use_kernel: bool = False, wire: str = 'f32'):
    """Run one SAFA round numerically.

    local_train_fn(stacked_params, *train_args) -> stacked trained params
    (it is responsible for vmapping over the clients dim).

    ``wire='int8'`` runs the compressed-wire fast path: the client
    uploads cross the simulated wire as one block-quantised int8 pack
    buffer and the server dequantises them in-register inside the fused
    Eq. 6-8 kernel (``ops.safa_compressed_update``) — exactly 2 kernel
    dispatches per round regardless of model depth.  ``use_kernel`` is
    ignored on that path (the fused kernel IS the aggregation).

    Returns (new_global, new_local, new_cache).
    """
    check_wire(wire)
    with obs.scope('rows'):
        base = distribute(global_w, local_w, sync_mask)
    with obs.scope('train'):
        trained = local_train_fn(base, *train_args)
    return safa_server_step(
        base, trained, cache, global_w, completed=completed, picked=picked,
        undrafted=undrafted, deprecated=deprecated, weights=weights,
        use_kernel=use_kernel, wire=wire)


# ---------------------------------------------------------------------------
# Compiled multi-round engines: jax.lax.scan over precomputed schedules
# ---------------------------------------------------------------------------
#
# The SAFA timing/event state machine (FLEnv draws, CFCFM selection, version
# bookkeeping) is pure numpy and independent of model weights, so every
# per-round mask can be precomputed into [k, m] schedules in one cheap host
# pass (federation.precompute_safa_schedule).  The whole numeric run then
# becomes ONE dispatch of a scanned round body with the (global, local,
# cache) carry donated — no per-round dispatch, no per-round host->device
# mask shuttling, no second full cache allocation.

class RoundSchedule(NamedTuple):
    """SAFA per-round masks, stacked [k, m] (plus round indices [k]) so k
    rounds cross host->device in a single transfer."""
    sync: Any
    completed: Any
    picked: Any
    undrafted: Any
    deprecated: Any
    round_idx: Any


class SyncSchedule(NamedTuple):
    """FedAvg/FedCS per-round masks, stacked [k, m]."""
    selected: Any
    completed: Any
    round_idx: Any


class LocalSchedule(NamedTuple):
    """Fully-local baseline per-round masks, stacked [k, m]: ``completed``
    is selected & survived — the only mask the numeric round needs."""
    completed: Any
    round_idx: Any


class AsyncSchedule(NamedTuple):
    """FedAsync per-round merge schedule, stacked [k, m]: the commit mask,
    the arrival-order merge permutation and the staleness-scaled mixing
    weights (0 for non-commits) — everything the sequential server mixes
    depend on, precomputed so the round body is schedule-driven."""
    committed: Any
    order: Any
    alphas: Any
    round_idx: Any


# -- the segment driver -------------------------------------------------------
#
# Each (protocol, state form) pair is one *step adapter*
# ``step(carry, row, weights, extra, **static) -> carry`` (the last
# section of this module): it hands one schedule row to the form's
# round function, with ``train_args=(row.round_idx, *extra)``.  ``_scan``
# runs a step over the rows of a segment, and ``_engines`` makes a form's
# two jitted programs from it: the single-run engine and its fleet twin,
# the ``vmap`` of the same scan.  The loop engine (``api``) applies the
# same step eagerly, one row at a time.

class Engine(NamedTuple):
    """What a runner needs beside a scan engine's name: the step adapter
    it scans (the loop engine applies it round by round) and the name of
    its fleet twin."""
    step: Callable
    fleet: str


#: scan engine name -> ``Engine``, one entry per state form
ENGINES: dict = {}

#: the engines' static keywords: every engine takes all four, and each
#: step reads the ones its round function needs
STATIC = ('local_train_fn', 'use_kernel', 'wire', 'spec')


def _scan(step, carry, schedule, weights, extra, static):
    """``step`` over every row of ``schedule``: the one ``lax.scan`` over
    rounds.  ``carry`` is the tuple of state trees."""
    def body(c, row):
        return step(c, row, weights, extra, **static), None

    carry, _ = jax.lax.scan(body, carry, schedule)
    return carry


def _engines(name: str, step, carry: int):
    """The jitted engine ``name`` (``<p>_run_scan<form>``) of one state
    form and its fleet twin (``<p>_run_fleet<form>``).

    Both take the ``carry`` state trees positionally, donated, so the
    caller's buffers are reused in place across the whole segment; then
    the segment's schedule (rows stacked [k, ...]) and the aggregation
    weights; then the keywords ``STATIC`` and ``extra``, a pytree handed
    to every train call after the round index
    (``local_train_fn(base, round_idx, *extra)``: a task's operands, a
    per-member data context).  Each returns the new carry, the tree
    itself when ``carry == 1``.  The fleet twin takes every argument,
    ``extra`` included, with a leading member axis [S, ...] and vmaps the
    same scan, so each member computes exactly the single-run program
    (a pallas_call in the round batches into one launch over a grid
    with a leading fleet dimension)."""
    fleet = name.replace('_run_scan', '_run_fleet')
    ENGINES[name] = Engine(step, fleet)

    def make(title, vmapped):
        def run(*args, extra=(), **static):
            state, (schedule, weights) = args[:carry], args[carry:]
            seg = lambda s, sch, w, e: _scan(step, s, sch, w, e, static)
            out = (jax.vmap(seg) if vmapped else seg)(state, schedule,
                                                      weights, extra)
            return out if carry > 1 else out[0]

        run.__name__ = run.__qualname__ = title
        return jax.jit(run, donate_argnums=tuple(range(carry)),
                       static_argnames=STATIC)

    return make(name, False), make(fleet, True)


# ---------------------------------------------------------------------------
# Baseline numeric rounds
# ---------------------------------------------------------------------------

def fedavg_round(global_w, local_w, *, selected, completed, weights,
                 local_train_fn, train_args=(), wire: str = 'f32'):
    """FedAvg: selected clients sync + train; aggregate over the selected
    clients that actually committed (renormalised weights); everyone else
    idles.  ``wire='int8'`` ships the uploads through the packed int8 wire
    (one quantize + one dequantize grid dispatch for the whole stacked
    tree — ``ops.wire_roundtrip_packed``), so the server aggregates what a
    compressed transfer actually delivers.  Returns (new_global,
    new_local)."""
    check_wire(wire)
    with obs.scope('rows'):
        base = distribute(global_w, local_w, selected)
    with obs.scope('train'):
        trained = local_train_fn(base, *train_args)
    return fedavg_server_step(base, trained, global_w, selected=selected,
                              completed=completed, weights=weights, wire=wire)


def fedavg_server_step(base, trained, global_w, *, selected, completed,
                       weights, wire: str = 'f32'):
    """FedAvg's post-train server math (wire transfer + renormalised
    aggregation + local sync), shared by the dense and sparse-schedule
    rounds so the two are trace-identical.  Returns (new_global,
    new_local)."""
    if wire == 'int8':
        from repro.kernels import ops as kops
        with obs.scope('wire'):
            trained = kops.wire_roundtrip_packed(trained, like=global_w)
    with obs.scope('aggregate'):
        ok = selected & completed
        wsum = jnp.maximum(jnp.sum(weights * ok), 1e-12)
        eff_w = jnp.where(ok, weights, 0.0) / wsum

        def red(t, g):
            w = eff_w.reshape((-1,) + (1,) * (t.ndim - 1)).astype(jnp.float32)
            agg = jnp.sum(t.astype(jnp.float32) * w, axis=0)
            any_ok = jnp.sum(ok) > 0
            return jnp.where(any_ok, agg,
                             g.astype(jnp.float32)).astype(g.dtype)

        new_global = jax.tree.map(red, trained, global_w)
    with obs.scope('rows'):
        new_local = masked_select(ok, trained, base)
    return new_global, new_local


def local_only_round(local_w, *, completed, local_train_fn, train_args=()):
    """Fully-local baseline: train, never aggregate."""
    with obs.scope('train'):
        trained = local_train_fn(local_w, *train_args)
    with obs.scope('rows'):
        return masked_select(completed, trained, local_w)


def fedasync_merge(global_w, trained, *, order, alphas):
    """FedAsync (Xie et al. [9]) server: merge updates one-by-one in arrival
    order with staleness-scaled mixing:

        w <- (1 - alpha_k) w + alpha_k w'_k

    trained: stacked [m, ...]; order: [m] int arrival permutation;
    alphas: [m] effective mixing weight per client (0 for non-commits).
    Returns the post-merge global model.
    """
    def merge(g, idx):
        a = alphas[idx].astype(jnp.float32)
        def mix(gl, tr):
            upd = tr[idx].astype(jnp.float32)
            return ((1.0 - a) * gl.astype(jnp.float32) + a * upd).astype(gl.dtype)
        return jax.tree.map(mix, g, trained), None

    new_global, _ = jax.lax.scan(merge, global_w, order)
    return new_global


# ---------------------------------------------------------------------------
# Sparse (active-set) schedules: [k, K] index + role tensors instead of
# [k, m] masks
# ---------------------------------------------------------------------------
#
# At production scale only O(quota) of the m clients touch a round: the
# sync/committed/deprecated sets.  A sparse schedule stores, per round, the
# indices of that active set (padded to a fixed capacity K with the sentinel
# index m) plus a per-slot role bitmask.  Every numeric state change of the
# dense round is covered — picked and undrafted are subsets of committed,
# and rows outside sync|committed|deprecated keep their local/cache entries
# bit-for-bit — so the dense masks are exactly reconstructible.
#
# Two execution modes consume the same schedule:
#   * 'sparse' (exact): train only the K active rows, scatter them into the
#     dense stacks, then run the *identical* dense server trace
#     (``safa_server_step``/``fedavg_server_step``).  FLOPs of local
#     training — the dominant cost — drop from O(m·train) to O(K·train);
#     memory stays O(m·N) for the carried state.  Bit-identical to dense.
#   * 'sparse_delta': update a carried running aggregate
#     ``agg = sum_k w_k cache_k`` from the K active rows only —
#     O(K·N) FLOPs per round, and for stateless protocols (FedAvg/FedCS)
#     no [m, N] buffer at all.  Equivalent to dense up to float summation
#     order (allclose, not bitwise).

# SAFA per-slot role bits (a slot may carry several: picked implies
# committed, deprecated clients are also synced, ...)
ROLE_SYNC = 1
ROLE_COMMITTED = 2
ROLE_PICKED = 4
ROLE_UNDRAFTED = 8
ROLE_DEPRECATED = 16

# synchronous-protocol (FedAvg/FedCS) role bits
SROLE_SELECTED = 1
SROLE_COMPLETED = 2


class SparseRoundSchedule(NamedTuple):
    """SAFA sparse per-round schedule: ``idx`` [k, K] int32 active-set row
    indices (sentinel m pads unused slots), ``roles`` [k, K] uint8 ROLE_*
    bitmasks, ``round_idx`` [k]."""
    idx: Any
    roles: Any
    round_idx: Any


class SparseSyncSchedule(NamedTuple):
    """FedAvg/FedCS sparse per-round schedule: ``idx`` [k, K] int32 selected
    row indices (sentinel m), ``roles`` [k, K] uint8 SROLE_* bitmasks,
    ``round_idx`` [k]."""
    idx: Any
    roles: Any
    round_idx: Any


class TierRoundSchedule(NamedTuple):
    """SAFA lag-tier per-round schedule: the sparse ``idx``/``roles``
    tensors plus [k, K] buffer-slot maps into the single value buffer the
    tier engines carry (``schedules.build_tier_schedule``).  ``base_src``/
    ``cache_src`` name the slots holding each active client's base model
    and cache row; ``cache_dst`` the slot its new cache row lands in
    (scratch == discard); ``global_dst`` [k] the slot the round's output
    global is recorded in."""
    idx: Any
    roles: Any
    base_src: Any
    cache_src: Any
    cache_dst: Any
    global_dst: Any
    round_idx: Any


def has_role(roles, bit):
    """Per-slot bool mask for one ROLE_*/SROLE_* bit."""
    return (roles & bit) != 0


def scatter_masks(idx, roles, m: int, bits):
    """Reconstruct dense [m] bool masks from one round's (idx, roles).

    Sentinel slots (idx == m) are dropped; returns one mask per bit in
    ``bits``, bit-equal to the dense precompute's masks."""
    return tuple(
        jnp.zeros((m,), bool).at[idx].set(has_role(roles, b), mode='drop')
        for b in bits)


def tree_gather(tree, idx):
    """Gather rows of every [m, ...] leaf.  Out-of-range (sentinel) indices
    clamp under jit — gathered padding rows are garbage by contract and
    must be masked by the caller's role bits."""
    return jax.tree.map(lambda a: a[idx], tree)


def tree_scatter(tree, idx, rows):
    """Scatter [K, ...] rows back into [m, ...] leaves; sentinel slots
    (idx == m) are dropped, all other rows are overwritten."""
    return jax.tree.map(lambda a, r: a.at[idx].set(r, mode='drop'),
                        tree, rows)


def _slot_weights(idx, weights):
    """Aggregation weight per slot, 0 at sentinel slots."""
    valid = idx < weights.shape[0]
    return jnp.where(valid, weights[idx], 0.0).astype(jnp.float32)


def init_aggregate(cache, weights):
    """The running aggregate carried by sparse_delta engines:
    ``agg = sum_k w_k cache_k`` as an f32 tree of global-shaped leaves.
    Computed once at run start from the dense cache; each round then
    adjusts it from the active rows only."""
    def red(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
        return jnp.sum(leaf.astype(jnp.float32) * w, axis=0)
    return jax.tree.map(red, cache)


def safa_round_sparse(global_w, local_w, cache, *, idx, roles, weights,
                      local_train_fn, train_args=(), use_kernel=False,
                      wire: str = 'f32'):
    """One SAFA round from a sparse schedule, bit-identical to
    ``safa_round`` on the dense masks that (idx, roles) encode.

    Only the K active rows are trained —
    ``local_train_fn(base_rows, rows, *train_args)`` is the rows-train
    contract (``Task.local_train_rows``) — then the raw trained rows are
    scattered over the dense base stack and the identical dense server
    trace runs.  Returns (new_global, new_local, new_cache)."""
    check_wire(wire)
    m = weights.shape[0]
    with obs.scope('rows'):
        sync_mask, completed, picked, undrafted, deprecated = scatter_masks(
            idx, roles, m, (ROLE_SYNC, ROLE_COMMITTED, ROLE_PICKED,
                            ROLE_UNDRAFTED, ROLE_DEPRECATED))
        base = distribute(global_w, local_w, sync_mask)
        base_rows = tree_gather(base, idx)
    with obs.scope('train'):
        trained_rows = local_train_fn(base_rows, idx, *train_args)
    with obs.scope('rows'):
        trained = tree_scatter(base, idx, trained_rows)
    return safa_server_step(
        base, trained, cache, global_w, completed=completed, picked=picked,
        undrafted=undrafted, deprecated=deprecated, weights=weights,
        use_kernel=use_kernel, wire=wire)


def safa_round_sparse_delta(global_w, local_w, cache, agg, *, idx, roles,
                            weights, local_train_fn, train_args=(),
                            wire: str = 'f32'):
    """One SAFA round in O(K·N): Eq. 6-8 as deltas on the carried running
    aggregate ``agg = sum_k w_k cache_k``.

        new_global = agg + sum_slots w (c1 - c_old)      (Eq. 6+7)
        new_agg    = new_global + sum_slots w (c2 - c1)  (Eq. 8)

    Only active cache/local rows are gathered, trained, and scattered
    back; no [m, N] intermediate is formed.  Equivalent to the dense round
    up to float summation order.  Returns (new_global, new_local,
    new_cache, new_agg)."""
    check_wire(wire)
    k = idx.shape[0]
    with obs.scope('rows'):
        sync_r = has_role(roles, ROLE_SYNC)
        com_r = has_role(roles, ROLE_COMMITTED)
        pick_r = has_role(roles, ROLE_PICKED)
        und_r = has_role(roles, ROLE_UNDRAFTED)
        dep_r = has_role(roles, ROLE_DEPRECATED)
        g_rows = broadcast_global(global_w, k)
        base_rows = masked_select(sync_r, g_rows, tree_gather(local_w, idx))
    with obs.scope('train'):
        trained_rows = local_train_fn(base_rows, idx, *train_args)
    if wire == 'int8':
        from repro.kernels import ops as kops
        with obs.scope('wire'):
            trained_rows = kops.wire_roundtrip_packed(trained_rows,
                                                      like=global_w)
    with obs.scope('aggregate'):
        trained_rows = masked_select(com_r, trained_rows, base_rows)
        c_rows = tree_gather(cache, idx)
        w_rows = _slot_weights(idx, weights)

        def delta(a, new, old):
            w = w_rows.reshape((-1,) + (1,) * (new.ndim - 1))
            return a + jnp.sum(
                (new.astype(jnp.float32) - old.astype(jnp.float32)) * w,
                axis=0)

        # Eq. 6 on the active rows only
        c1_rows = masked_select(dep_r & ~pick_r, g_rows, c_rows)
        c1_rows = masked_select(pick_r, trained_rows, c1_rows)
        # Eq. 7: the full weighted sum moves by the rows that changed
        agg1 = jax.tree.map(delta, agg, c1_rows, c_rows)
        new_global = jax.tree.map(lambda a, g: a.astype(g.dtype), agg1,
                                  global_w)
        # Eq. 8: undrafted arrivals enter the cache for the next round
        c2_rows = masked_select(und_r, trained_rows, c1_rows)
        new_agg = jax.tree.map(delta, agg1, c2_rows, c1_rows)
    with obs.scope('rows'):
        new_cache = tree_scatter(cache, idx, c2_rows)
        new_local = tree_scatter(local_w, idx, trained_rows)
    return new_global, new_local, new_cache, new_agg


def fedavg_round_sparse(global_w, local_w, *, idx, roles, weights,
                        local_train_fn, train_args=(), wire: str = 'f32'):
    """FedAvg round from a sparse schedule, bit-identical to
    ``fedavg_round``: train the selected rows only, scatter, then run the
    dense server trace.  Returns (new_global, new_local)."""
    check_wire(wire)
    m = weights.shape[0]
    with obs.scope('rows'):
        selected, completed = scatter_masks(
            idx, roles, m, (SROLE_SELECTED, SROLE_COMPLETED))
        base = distribute(global_w, local_w, selected)
        base_rows = tree_gather(base, idx)
    with obs.scope('train'):
        trained_rows = local_train_fn(base_rows, idx, *train_args)
    with obs.scope('rows'):
        trained = tree_scatter(base, idx, trained_rows)
    return fedavg_server_step(base, trained, global_w, selected=selected,
                              completed=completed, weights=weights, wire=wire)


def fedavg_round_sparse_delta(global_w, *, idx, roles, weights,
                              local_train_fn, train_args=(),
                              wire: str = 'f32'):
    """Stateless O(K·N) FedAvg round: selected clients always sync to the
    global model, and a client's local model never feeds back into the
    aggregate (it is overwritten by the sync on its next selection), so no
    [m, N] local stack needs to exist at all — the only carried state is
    the global model.  Equivalent to the dense round up to float summation
    order.  Returns new_global."""
    check_wire(wire)
    k = idx.shape[0]
    with obs.scope('rows'):
        com_r = has_role(roles, SROLE_COMPLETED) & (idx < weights.shape[0])
        base_rows = broadcast_global(global_w, k)
    with obs.scope('train'):
        trained_rows = local_train_fn(base_rows, idx, *train_args)
    if wire == 'int8':
        from repro.kernels import ops as kops
        with obs.scope('wire'):
            trained_rows = kops.wire_roundtrip_packed(trained_rows,
                                                      like=global_w)
    with obs.scope('aggregate'):
        w_rows = jnp.where(com_r, _slot_weights(idx, weights), 0.0)
        wsum = jnp.maximum(jnp.sum(w_rows), 1e-12)
        eff_w = w_rows / wsum
        any_ok = jnp.sum(com_r) > 0

        def red(t, g):
            w = eff_w.reshape((-1,) + (1,) * (t.ndim - 1))
            agg = jnp.sum(t.astype(jnp.float32) * w, axis=0)
            return jnp.where(any_ok, agg,
                             g.astype(jnp.float32)).astype(g.dtype)

        return jax.tree.map(red, trained_rows, global_w)


# -- packed sparse-delta engine: rows kernels on resident pack buffers ------

def safa_round_sparse_delta_packed(gbuf, lbuf, cbuf, abuf, *, idx, roles,
                                   weights, local_train_fn, train_args=(),
                                   spec, wire: str = 'f32'):
    """One O(K·N) SAFA round entirely on pack buffers, aggregation fused.

    gbuf [N] f32 global pack; lbuf/cbuf [m+1, N] local/cache packs (the
    trailing scratch row absorbs sentinel slots); abuf [N] f32 running
    aggregate.  Active rows move through ``ops.gather_rows`` -> unpack ->
    rows-train -> repack -> one ``safa_aggregate_packed_rows`` dispatch
    (Eq. 6-8 + both delta sums fused) -> ``ops.scatter_rows`` writes the
    cache/local rows back in place.  Under ``wire='int8'`` the repacked
    rows are block-quantised and the q8 rows kernel dequantises
    in-register (``spec`` must then be the QBLOCK-aligned ``wire_spec``).
    Allclose- (not bit-) equivalent to ``safa_round_sparse_delta`` — the
    kernel accumulates slot-by-slot over tiles instead of one tree-wide
    sum.  Returns (gbuf', lbuf', cbuf', abuf')."""
    check_wire(wire)
    from repro.kernels import ops as kops
    with obs.scope('rows'):
        com_r = has_role(roles, ROLE_COMMITTED)
        pick_r = has_role(roles, ROLE_PICKED)
        und_r = has_role(roles, ROLE_UNDRAFTED)
        dep_r = has_role(roles, ROLE_DEPRECATED)
        sync_r = has_role(roles, ROLE_SYNC)
        w_rows = _slot_weights(idx, weights)
        l_rows = kops.gather_rows(lbuf, idx)
        base_rows = jnp.where(sync_r[:, None], gbuf[None].astype(lbuf.dtype),
                              l_rows)
        unpacked = kops.unpack_stacked(base_rows, spec)
    with obs.scope('train'):
        trained = local_train_fn(unpacked, idx, *train_args)
    with obs.scope('rows'):
        trained = kops.pack_stacked(trained, spec)
    if wire == 'int8':
        with obs.scope('wire'):
            q, scales = kops.quantize_packed(trained)
        with obs.scope('aggregate'):
            ng, na, c2_rows, local_rows = kops.safa_aggregate_packed_q8_rows(
                q, scales, base_rows, cbuf, gbuf, abuf, idx, pick_r, und_r,
                dep_r, com_r, w_rows)
    else:
        with obs.scope('aggregate'):
            local_rows = jnp.where(com_r[:, None], trained, base_rows)
            ng, na, c2_rows = kops.safa_aggregate_packed_rows(
                cbuf, local_rows, gbuf, abuf, idx, pick_r, und_r, dep_r,
                w_rows)
    with obs.scope('rows'):
        new_c = kops.scatter_rows(cbuf, idx, c2_rows.astype(cbuf.dtype))
        new_l = kops.scatter_rows(lbuf, idx, local_rows.astype(lbuf.dtype))
    return ng.astype(gbuf.dtype), new_l, new_c, na


# -- lag-tier engine: version ring + active slab instead of [m, N] stacks ---
#
# SAFA's lag-tolerant distribution (Eq. 2-3) bounds every client's lag by
# tau, and a committed client is force-synced the next round it appears —
# so a trained local row is never read back, and every base model a round
# reads is a *global version snapshot* (at most tau+2 live at once).  Cache
# rows are such snapshots or commit rows of recently active clients.  The
# tier round therefore carries ONE value buffer ``buf`` of
# ``capacity + 1`` rows (capacity = peak live distinct rows, O(tau+quota);
# the trailing row is scratch) and replays the host-precomputed slot maps:
# gather bases at ``base_src``, caches at ``cache_src``, run the exact
# sparse_delta slot math, scatter the new cache rows to ``cache_dst`` and
# record the round's output global at ``global_dst``.  Per round the
# written slots are disjoint from the read slots (a value written in round
# t is first read strictly later), which lets the packed kernels alias the
# buffer in place.  Memory: O((tau+quota)·N), independent of m.

def safa_round_sparse_tier(global_w, buf, agg, *, idx, roles, base_src,
                           cache_src, cache_dst, global_dst, weights,
                           local_train_fn, train_args=(), wire: str = 'f32'):
    """One SAFA round in O((tau+quota)·N) via the lag-tier value buffer.

    Identical slot math to ``safa_round_sparse_delta`` — base/cache rows
    are simply gathered through the slot indirection instead of per-client
    stacks — so the two agree wherever both run (and both are equivalent
    to the dense round up to float summation order).  Returns
    (new_global, new_buf, new_agg)."""
    check_wire(wire)
    k = idx.shape[0]
    with obs.scope('rows'):
        sync_r = has_role(roles, ROLE_SYNC)
        com_r = has_role(roles, ROLE_COMMITTED)
        pick_r = has_role(roles, ROLE_PICKED)
        und_r = has_role(roles, ROLE_UNDRAFTED)
        dep_r = has_role(roles, ROLE_DEPRECATED)
        g_rows = broadcast_global(global_w, k)
        base_rows = masked_select(sync_r, g_rows, tree_gather(buf, base_src))
    with obs.scope('train'):
        trained_rows = local_train_fn(base_rows, idx, *train_args)
    if wire == 'int8':
        from repro.kernels import ops as kops
        with obs.scope('wire'):
            trained_rows = kops.wire_roundtrip_packed(trained_rows,
                                                      like=global_w)
    with obs.scope('aggregate'):
        trained_rows = masked_select(com_r, trained_rows, base_rows)
        c_rows = tree_gather(buf, cache_src)
        w_rows = _slot_weights(idx, weights)

        def delta(a, new, old):
            w = w_rows.reshape((-1,) + (1,) * (new.ndim - 1))
            return a + jnp.sum(
                (new.astype(jnp.float32) - old.astype(jnp.float32)) * w,
                axis=0)

        c1_rows = masked_select(dep_r & ~pick_r, g_rows, c_rows)
        c1_rows = masked_select(pick_r, trained_rows, c1_rows)
        agg1 = jax.tree.map(delta, agg, c1_rows, c_rows)
        new_global = jax.tree.map(lambda a, g: a.astype(g.dtype), agg1,
                                  global_w)
        c2_rows = masked_select(und_r, trained_rows, c1_rows)
        new_agg = jax.tree.map(delta, agg1, c2_rows, c1_rows)
    with obs.scope('rows'):
        new_buf = tree_scatter(buf, cache_dst, c2_rows)
        new_buf = jax.tree.map(
            lambda b, g: b.at[global_dst].set(g.astype(b.dtype)), new_buf,
            new_global)
    return new_global, new_buf, new_agg


def safa_round_sparse_tier_packed(gbuf, tbuf, abuf, *, idx, roles, base_src,
                                  cache_src, cache_dst, global_dst, weights,
                                  local_train_fn, train_args=(), spec,
                                  wire: str = 'f32'):
    """Packed-buffer lag-tier round: gbuf [N] f32, tbuf [capacity+1,
    N/128, 128] value buffer (a row slab: each row whole (8, 128) tiles,
    so the kernels copy exact rows), abuf [N] f32 running aggregate.  One
    fused tier-rows dispatch does Eq. 6-8, both delta sums, and the
    ``cache_dst`` scatter in place (the buffer aliases through the
    kernel); only the ``global_dst`` row write remains outside.  Returns
    (gbuf', tbuf', abuf')."""
    check_wire(wire)
    from repro.kernels import ops as kops
    with obs.scope('rows'):
        sync_r = has_role(roles, ROLE_SYNC)
        com_r = has_role(roles, ROLE_COMMITTED)
        pick_r = has_role(roles, ROLE_PICKED)
        und_r = has_role(roles, ROLE_UNDRAFTED)
        dep_r = has_role(roles, ROLE_DEPRECATED)
        w_rows = _slot_weights(idx, weights)
        b_rows = kops.gather_rows(tbuf, base_src)
        base_rows = jnp.where(sync_r[:, None], gbuf[None].astype(tbuf.dtype),
                              b_rows)
        unpacked = kops.unpack_stacked(base_rows, spec)
    with obs.scope('train'):
        trained = local_train_fn(unpacked, idx, *train_args)
    with obs.scope('rows'):
        trained = kops.pack_stacked(trained, spec)
    if wire == 'int8':
        with obs.scope('wire'):
            q, scales = kops.quantize_packed(trained)
        with obs.scope('aggregate'):
            ng, na, new_t = kops.safa_aggregate_packed_q8_tier_rows(
                q, scales, base_rows, tbuf, gbuf, abuf, cache_src, cache_dst,
                pick_r, und_r, dep_r, com_r, w_rows)
    else:
        with obs.scope('aggregate'):
            local_rows = jnp.where(com_r[:, None], trained, base_rows)
            ng, na, new_t = kops.safa_aggregate_packed_tier_rows(
                tbuf, local_rows, gbuf, abuf, cache_src, cache_dst, pick_r,
                und_r, dep_r, w_rows)
    with obs.scope('rows'):
        new_t = new_t.at[global_dst].set(
            ng.reshape(new_t.shape[1:]).astype(new_t.dtype))
    return ng.astype(gbuf.dtype), new_t, na


def fedasync_round(global_w, local_w, *, committed, order, alphas,
                   local_train_fn, train_args=()):
    """One full numeric FedAsync round: every client trains, crashed/late
    clients are masked out, the server merges the arrivals one-by-one
    (``fedasync_merge``), and committed clients pull the fresh global
    model.  Shared by the per-round loop engine and the scan body so the
    two stay step-identical.  Returns (new_global, new_local)."""
    m = committed.shape[0]
    with obs.scope('train'):
        trained = local_train_fn(local_w, *train_args)
    with obs.scope('rows'):
        trained = masked_select(committed, trained, local_w)
    with obs.scope('aggregate'):
        new_global = fedasync_merge(global_w, trained, order=order,
                                    alphas=alphas)
    with obs.scope('rows'):
        # committed clients pull the fresh global model
        new_local = masked_select(committed, broadcast_global(new_global, m),
                                  masked_select(committed, trained, local_w))
    return new_global, new_local


# ---------------------------------------------------------------------------
# Weighted-merge engine: the staleness-adaptive aggregation family
# ---------------------------------------------------------------------------
#
# SEAFL-style adaptive weighting, CSAFL-style per-cluster semi-async
# aggregation, and (via an exact host-side fold of the sequential merge
# recursion) the FedAsync s(dt) discount family all lower to one schedule
# representation: a precomputed [rounds, m] weight row ``wrow`` with
#
#     new_global = (1 - sum(wrow)) * global + sum_k wrow[k] * trained_k
#
# The row is zero off the committed set, so one round body — and therefore
# one scan/fleet engine — replays every scheme in the family.  Cluster
# structure (CSAFL) folds in host-side: wrow[k] = alpha_g * what_k where
# alpha_g is cluster g's mixing coefficient and what_k the intra-cluster
# weight, so the kernel path below computes the masked per-cluster
# sub-aggregates implicitly through the weight operand.

class WeightedSchedule(NamedTuple):
    """Weighted-merge per-round schedule, stacked [k, m]: the commit mask
    and the precomputed per-client merge weights (0 for non-commits)."""
    committed: Any
    wrow: Any
    round_idx: Any


def weighted_merge(global_w, trained, *, wrow, use_kernel=False):
    """One-shot weighted server merge:

        w <- (1 - sum_k wrow_k) w + sum_k wrow_k w'_k

    trained: stacked [m, ...]; wrow: [m] f32 effective merge weight per
    client (0 for non-commits; sum(wrow) <= 1).  ``use_kernel='packed'``
    routes the fused single-dispatch Pallas path
    (``ops.weighted_merge_tree_packed``).  Returns the post-merge global
    model."""
    if use_kernel == 'packed':
        from repro.kernels import ops as kops
        return kops.weighted_merge_tree_packed(trained, global_w, wrow=wrow)
    residual = (1.0 - jnp.sum(wrow)).astype(jnp.float32)

    def mix(g, t):
        w = wrow.reshape((-1,) + (1,) * (t.ndim - 1)).astype(jnp.float32)
        agg = jnp.sum(t.astype(jnp.float32) * w, axis=0)
        return (residual * g.astype(jnp.float32) + agg).astype(g.dtype)

    return jax.tree.map(mix, global_w, trained)


@functools.partial(jax.jit, static_argnames=('local_train_fn', 'use_kernel',
                                             'wire'))
def weighted_round(global_w, local_w, *, committed, wrow, local_train_fn,
                   train_args=(), use_kernel=False, wire: str = 'f32'):
    """One full numeric weighted-merge round: every client trains from its
    local model, crashed/late clients are masked out, the server applies
    the precomputed weight row in ONE batched merge, and committed clients
    pull the fresh global model (non-commits keep training on their stale
    copy — that is what makes the precomputed staleness meaningful).

    Jitted (unlike the sequential-merge rounds, whose float math all sits
    inside an inner ``lax.scan`` and therefore always compiles): the
    one-shot merge is plain elementwise math, and the loop engine must
    execute the same compiled expressions as the scan body or the two
    drift by an fma contraction.

    ``wire='int8'`` round-trips the uploads through the packed int8 wire
    (``ops.wire_roundtrip_packed``) before the merge — the server merges
    what a compressed transfer actually delivers; non-committed clients
    never upload, so their local state stays un-quantised.  Returns
    (new_global, new_local)."""
    check_wire(wire)
    m = committed.shape[0]
    with obs.scope('train'):
        trained = local_train_fn(local_w, *train_args)
    with obs.scope('rows'):
        trained = masked_select(committed, trained, local_w)
    uploads = trained
    if wire == 'int8':
        from repro.kernels import ops as kops
        with obs.scope('wire'):
            uploads = kops.wire_roundtrip_packed(trained, like=global_w)
    with obs.scope('aggregate'):
        new_global = weighted_merge(global_w, uploads, wrow=wrow,
                                    use_kernel=use_kernel)
    with obs.scope('rows'):
        new_local = masked_select(committed, broadcast_global(new_global, m),
                                  trained)
    return new_global, new_local


# ---------------------------------------------------------------------------
# Step adapters and their engines: one pair per (protocol, state form)
# ---------------------------------------------------------------------------
#
# A step hands one schedule row to its form's round function; the line
# after it binds the form's engine and fleet twin.  An engine's name is
# its program's name (``jit(safa_run_scan)``), by which traces, tests and
# fault plants find it on this module.  The carries are the runner's
# ``_RunState`` fields (``api.Form``); the packed forms carry pack
# buffers, which callers pack once before and unpack once after a run.

def _safa_step(carry, row, weights, extra, *, local_train_fn,
               use_kernel=False, wire='f32', **_):
    """Dense SAFA: carry (global, local, cache), ``RoundSchedule`` rows."""
    return safa_round(
        *carry, sync_mask=row.sync, completed=row.completed,
        picked=row.picked, undrafted=row.undrafted,
        deprecated=row.deprecated, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        use_kernel=use_kernel, wire=wire)


safa_run_scan, safa_run_fleet = _engines('safa_run_scan', _safa_step, 3)


def _safa_sparse_step(carry, row, weights, extra, *, local_train_fn,
                      use_kernel=False, wire='f32', **_):
    """Sparse SAFA: the dense carry, ``SparseRoundSchedule`` rows; only
    the K active rows train (``local_train_fn`` is the rows-train
    contract, ``Task.local_train_rows``)."""
    return safa_round_sparse(
        *carry, idx=row.idx, roles=row.roles, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        use_kernel=use_kernel, wire=wire)


safa_run_scan_sparse, safa_run_fleet_sparse = _engines(
    'safa_run_scan_sparse', _safa_sparse_step, 3)


def _safa_sparse_delta_step(carry, row, weights, extra, *, local_train_fn,
                            wire='f32', **_):
    """O(K·N) SAFA: carry (global, local, cache, agg) with
    ``agg = init_aggregate(cache, weights)`` at entry."""
    return safa_round_sparse_delta(
        *carry, idx=row.idx, roles=row.roles, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        wire=wire)


safa_run_scan_sparse_delta, safa_run_fleet_sparse_delta = _engines(
    'safa_run_scan_sparse_delta', _safa_sparse_delta_step, 4)


def _safa_sparse_delta_packed_step(carry, row, weights, extra, *,
                                   local_train_fn, spec, wire='f32', **_):
    """O(K·N) SAFA on pack buffers: carry (global [N], local [m+1, N],
    cache [m+1, N], agg [N]); ``spec`` is the pack layout
    (``ops.wire_spec`` under ``wire='int8'``, ``ops.pack_spec``
    otherwise)."""
    return safa_round_sparse_delta_packed(
        *carry, idx=row.idx, roles=row.roles, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        spec=spec, wire=wire)


safa_run_scan_sparse_delta_packed, safa_run_fleet_sparse_delta_packed = \
    _engines('safa_run_scan_sparse_delta_packed',
             _safa_sparse_delta_packed_step, 4)


def _tier_maps(row):
    """A ``TierRoundSchedule`` row's active set and slot maps."""
    return dict(idx=row.idx, roles=row.roles, base_src=row.base_src,
                cache_src=row.cache_src, cache_dst=row.cache_dst,
                global_dst=row.global_dst)


def _safa_sparse_tier_step(carry, row, weights, extra, *, local_train_fn,
                           wire='f32', **_):
    """Lag-tier SAFA: carry (global, value buffer, agg) with
    ``buf = broadcast(global)`` over capacity+1 rows and
    ``agg = global * sum(weights)`` at entry; no [m, N] stack exists
    anywhere in the program."""
    return safa_round_sparse_tier(
        *carry, **_tier_maps(row), weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        wire=wire)


safa_run_scan_sparse_tier, safa_run_fleet_sparse_tier = _engines(
    'safa_run_scan_sparse_tier', _safa_sparse_tier_step, 3)


def _safa_sparse_tier_packed_step(carry, row, weights, extra, *,
                                  local_train_fn, spec, wire='f32', **_):
    """Lag-tier SAFA on pack buffers: carry (global [N], row slab
    [capacity+1, N/128, 128], agg [N]), O((tau+quota)·N) bytes."""
    return safa_round_sparse_tier_packed(
        *carry, **_tier_maps(row), weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        spec=spec, wire=wire)


safa_run_scan_sparse_tier_packed, safa_run_fleet_sparse_tier_packed = \
    _engines('safa_run_scan_sparse_tier_packed',
             _safa_sparse_tier_packed_step, 3)


def _fedavg_step(carry, row, weights, extra, *, local_train_fn, wire='f32',
                 **_):
    """FedAvg/FedCS: carry (global, local), ``SyncSchedule`` rows."""
    return fedavg_round(
        *carry, selected=row.selected, completed=row.completed,
        weights=weights, local_train_fn=local_train_fn,
        train_args=(row.round_idx, *extra), wire=wire)


fedavg_run_scan, fedavg_run_fleet = _engines('fedavg_run_scan',
                                             _fedavg_step, 2)


def _fedavg_sparse_step(carry, row, weights, extra, *, local_train_fn,
                        wire='f32', **_):
    """Sparse FedAvg/FedCS: carry (global, local); trains the selected
    rows only."""
    return fedavg_round_sparse(
        *carry, idx=row.idx, roles=row.roles, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        wire=wire)


fedavg_run_scan_sparse, fedavg_run_fleet_sparse = _engines(
    'fedavg_run_scan_sparse', _fedavg_sparse_step, 2)


def _fedavg_sparse_delta_step(carry, row, weights, extra, *,
                              local_train_fn, wire='f32', **_):
    """Stateless FedAvg/FedCS: the global model is the whole carry, so
    peak device memory is O(N + K·N), independent of m."""
    return (fedavg_round_sparse_delta(
        *carry, idx=row.idx, roles=row.roles, weights=weights,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        wire=wire),)


fedavg_run_scan_sparse_delta, fedavg_run_fleet_sparse_delta = _engines(
    'fedavg_run_scan_sparse_delta', _fedavg_sparse_delta_step, 1)


def _local_step(carry, row, weights, extra, *, local_train_fn, **_):
    """Fully local: the local stack is the whole carry; the caller
    aggregates at eval points."""
    del weights
    return (local_only_round(
        *carry, completed=row.completed, local_train_fn=local_train_fn,
        train_args=(row.round_idx, *extra)),)


local_run_scan, local_run_fleet = _engines('local_run_scan', _local_step, 1)


def _fedasync_step(carry, row, weights, extra, *, local_train_fn, **_):
    """FedAsync: carry (global, local); the mixing weights live in the
    schedule's merge-order/alpha rows, merged by an inner scan."""
    del weights
    return fedasync_round(
        *carry, committed=row.committed, order=row.order, alphas=row.alphas,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra))


fedasync_run_scan, fedasync_run_fleet = _engines('fedasync_run_scan',
                                                 _fedasync_step, 2)


def _weighted_step(carry, row, weights, extra, *, local_train_fn,
                   use_kernel=False, wire='f32', **_):
    """The weighted-merge family: carry (global, local); the scheme is
    the schedule's weight rows, so members of one fleet may replay
    different schemes."""
    del weights
    return weighted_round(
        *carry, committed=row.committed, wrow=row.wrow,
        local_train_fn=local_train_fn, train_args=(row.round_idx, *extra),
        use_kernel=use_kernel, wire=wire)


weighted_run_scan, weighted_run_fleet = _engines('weighted_run_scan',
                                                 _weighted_step, 2)
