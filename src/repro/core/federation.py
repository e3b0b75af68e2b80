"""Federation event processes: SAFA / FedAvg / FedCS / FedAsync / local.

This module owns the *protocol state machines* (versions, commit flags,
pending straggler progress) in numpy: they drive the event simulator for
timing/crash draws and precompute whole runs — and whole sweeps — as mask
schedules, because the event process never looks at model weights.

* ``precompute_safa_schedule`` / ``precompute_sync_schedule`` /
  ``precompute_local_schedule`` / ``precompute_fedasync_schedule`` run a
  single simulation's state machine in one host pass and emit
  ``[rounds, m]`` mask schedules (containers in ``repro.core.schedules``).
* ``precompute_fleet_schedule`` / ``precompute_sync_fleet_schedule`` run S
  state machines fleet-major on ``[S, m]`` arrays, bit-identical to S
  independent precomputes.

Execution lives elsewhere: the compiled scan/fleet engines are in
``repro.core.protocol``, and the public entry point that wires specs,
schedules and engines together is ``repro.core.api`` (``repro.api``) —
declarative ``Experiment``s with checkpoint/resume-capable runners.

The historical free functions (``run_safa``, ``run_fedavg``, ``run_fedcs``,
``run_local``, ``run_fedasync``, ``run_sweep``) remain as thin shims over
``api.Experiment`` for backwards compatibility; they emit
``DeprecationWarning`` and are bit-identical to their spec spellings
(regression-tested).
"""
from __future__ import annotations

import sys
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import protocol, schedules, selection
from repro.core.schedules import (
    AsyncFleetSchedule,
    FedasyncSchedule,
    FleetSchedule,
    History,
    LocalFleetSchedule,
    LocalSchedule,
    RoundRecord,
    SafaSchedule,
    SweepMember,
    SyncFleetSchedule,
    SyncSchedule,
)
from repro.fedsim import FLEnv

__all__ = [
    'AsyncFleetSchedule', 'FedasyncSchedule', 'FleetSchedule', 'History',
    'LocalFleetSchedule', 'LocalSchedule', 'RoundRecord', 'RUNNERS',
    'SafaSchedule', 'SweepMember', 'SyncFleetSchedule', 'SyncSchedule',
    'Task', 'precompute_fedasync_schedule', 'precompute_fleet_schedule',
    'precompute_local_schedule', 'precompute_safa_schedule',
    'precompute_sync_fleet_schedule', 'precompute_sync_schedule',
    'run_fedasync', 'run_fedavg', 'run_fedcs', 'run_local', 'run_safa',
    'run_sweep',
]


class Task:
    """A federated learning task: model init/train/eval, model-agnostic for
    the protocol layer.  ``local_train(stacked_params, round_idx)`` must
    train every client replica for E epochs (vmapped inside).

    ``round_idx`` is an int32 scalar: a device array under
    ``engine='loop'``, traced under the scanned engines — implementations
    must not branch on it in Python (use ``jnp.where``/``lax.cond`` if
    the round number matters)."""

    def init_global(self, key):
        raise NotImplementedError

    def local_train(self, stacked_params, round_idx):
        raise NotImplementedError

    def local_train_rows(self, params_rows, rows, round_idx):
        """Sparse-schedule training: train only the K client replicas in
        ``params_rows`` ([K, ...] leaves), whose client ids are ``rows``
        ([K] int32, device array; sentinel ids >= m gather-clamp to
        garbage rows whose output the engine discards).  Must produce, row
        for row, the same bits ``local_train`` produces for those clients —
        that is the sparse==dense contract."""
        raise NotImplementedError(
            f'{type(self).__name__} does not implement local_train_rows; '
            f'sparse schedules need the rows-train contract')

    def evaluate(self, global_params) -> dict:
        raise NotImplementedError

    def operands(self):
        """Device arrays training reads but does not train (frozen
        weights, client data), as a pytree, or None.  The dense SAFA scan
        engine passes them to its compiled segment as arguments and on to
        ``local_train(stacked, round_idx, operands)``, so a large operand
        never becomes a program constant."""
        return None


class _NumericState:
    def __init__(self, task: Task, m: int, seed: int):
        key = jax.random.PRNGKey(seed)
        self.global_w = task.init_global(key)
        self.local_w = protocol.broadcast_global(self.global_w, m)
        self.cache = protocol.broadcast_global(self.global_w, m)


def _masked_var(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Population variance of ``values`` over ``mask`` along the last axis
    (0.0 where the mask is empty).

    Formulated as masked sums so the single-run and fleet-major schedule
    precomputes reduce in the same order and agree bit for bit."""
    n = mask.sum(axis=-1)
    denom = np.maximum(n, 1)
    mean = np.sum(np.where(mask, values, 0), axis=-1) / denom
    dev = np.where(mask, (values - mean[..., None]) ** 2, 0.0)
    return np.where(n > 0, np.sum(dev, axis=-1) / denom, 0.0)


def precompute_safa_schedule(env: FLEnv, *, fraction: float,
                             lag_tolerance: int, rounds: int,
                             form: str = 'dense'):
    """Run the SAFA timing/event state machine (Eq. 3 version bookkeeping,
    crash draws, CFCFM selection) for all rounds in one numpy host pass.

    The event process never reads model weights, so the full [rounds, m]
    mask schedule — and every timing metric — is known up front.  Consumes
    ``env``'s rng exactly as the seed's round-by-round loop did.

    ``form='sparse'`` emits a compact ``SparseSchedule`` instead: the SAME
    loop runs (same draws, same selection, same records), but each round
    stores only its active set's (idx, roles) pair, so peak host memory is
    O(m + rounds * K) instead of O(rounds * m).  By construction
    ``precompute(form='sparse')`` equals ``precompute(form='dense')
    .to_sparse()`` exactly — one event stream, two encodings.

    ``form='sparse_tier'`` additionally records each active client's base
    version (the ``v`` counter this loop already maintains) and lowers the
    event stream to a ``TierSchedule``: sparse rows plus the slot maps
    that let the numeric engines carry one O(lag_tolerance + quota)-row
    value buffer instead of [m, N] local/cache stacks.  Equals
    ``precompute(form='dense').to_tier()`` exactly.
    """
    if form not in ('dense', 'sparse', 'sparse_tier'):
        raise ValueError(f"unknown form {form!r} (want 'dense', 'sparse', "
                         f"or 'sparse_tier')")
    m = env.m
    v = np.zeros(m, dtype=int)             # base-model versions
    committed_prev = np.ones(m, bool)      # round 1: everyone holds w(0)
    picked_prev = np.zeros(m, bool)
    pending = np.zeros(m)                  # straggler partial progress (fraction)
    with obs.span('precompute.draw'):
        tim = env.round_timing(rounds)     # [rounds, m] trace/wire-aware
        crashed_all, cfrac_all = env.draw_rounds(rounds)
    work = env.n_batches * env.epochs      # per-round work units
    wasted = 0.0
    performed = 0.0
    masks = {k: np.zeros((rounds, m), bool)
             for k in ('sync', 'committed', 'picked', 'undrafted',
                       'deprecated')} if form == 'dense' else None
    sparse_rows = []
    base_v_rows = []
    records = []
    with obs.span('precompute.events'):
        for t in range(1, rounds + 1):
            gv = t - 1
            up, dep, _ = protocol.classify_versions(v, gv, lag_tolerance,
                                                    committed_prev)
            sync = up | dep
            # forced sync discards any pending straggler progress (futility);
            # masked-sum form so the fleet-major precompute reduces identically
            wasted += float(np.sum(np.where(sync, pending * work, 0.0)))
            pending[sync] = 0.0
            v[sync] = gv

            crashed, cfrac = crashed_all[t - 1], cfrac_all[t - 1]
            remaining = 1.0 - pending
            t_train = remaining * tim.full_tt[t - 1]
            t_dist = env.t_dist(int(sync.sum()))
            # every live client uploads; sync'd ones first download the global
            # (== t_updown * (1 + sync) bitwise when the traces are constant)
            arrival = t_dist + (tim.t_up[t - 1] + sync * tim.t_down[t - 1]) \
                + t_train
            completed = ~crashed
            arrival = np.where(completed, arrival, np.inf)
            performed += float(np.sum(np.where(completed, remaining,
                                               cfrac * remaining) * work))
            base_versions = v.copy()

            sel = selection.cfcfm(arrival, completed, picked_prev, fraction,
                                  env.t_lim)
            pending = np.where(crashed,
                               np.minimum(pending + cfrac * remaining, 0.999),
                               pending)
            pending[sel.committed] = 0.0
            v[sel.committed] = t

            if form == 'dense':
                i = t - 1
                masks['sync'][i] = sync
                masks['committed'][i] = sel.committed
                masks['picked'][i] = sel.picked
                masks['undrafted'][i] = sel.undrafted
                masks['deprecated'][i] = dep
            else:
                row = schedules.safa_sparse_row(
                    sync, sel.committed, sel.picked, sel.undrafted, dep,
                    bootstrap=(t == 1))
                sparse_rows.append(row)
                if form == 'sparse_tier':
                    base_v_rows.append(base_versions[row[0]])

            records.append(RoundRecord(
                round=t,
                round_len=min(env.t_lim, sel.quota_met_time),
                t_dist=t_dist,
                eur=float(sel.picked.sum()) / m,
                sr=float(sync.sum()) / m,
                vv=float(_masked_var(base_versions, sel.committed)),
                n_picked=int(sel.picked.sum()),
                n_committed=int(sel.committed.sum()),
                n_crashed=int(crashed.sum()),
            ))
            committed_prev = sel.committed.copy()
            picked_prev = sel.picked.copy()

    futility = wasted / max(performed, 1e-9)
    if form == 'dense':
        return SafaSchedule(records=records, futility=futility, **masks)
    with obs.span('precompute.lower'):
        if form == 'sparse_tier':
            return schedules.build_tier_schedule(m, sparse_rows, base_v_rows,
                                                 records, futility)
        idx, roles = schedules.pack_sparse_rows(sparse_rows, m)
        return schedules.SparseSchedule(m=m, idx=idx, roles=roles,
                                        records=records, futility=futility)


def _quantized_train_fn(base_fn):
    """int8-compressed uplink, per-leaf REFERENCE path (comm_quant kernel):
    each client quantises each leaf of its own update independently —
    exactly what a real compressed transfer carries — costing 2 pallas
    dispatches per leaf per client.  This is the bit-identity ground truth
    for the packed fast path (``wire='int8'``), which ships the same
    numbers in 2 dispatches total.

    The wrapper is memoised on the owning Task, keyed by the wrapped
    function, so it stays a stable static argument to ``safa_run_scan``
    (a fresh closure per run would retrace the whole scanned program)
    without pinning Tasks beyond their own lifetime — and without
    handing back a stale closure when a *different* bound method of the
    same Task gets wrapped later."""
    def train_fn(stacked, *args):
        from repro.kernels import ops as kops
        trained = base_fn(stacked, *args)

        def per_leaf(x):
            flat = x.reshape(x.shape[0], -1)
            rows = [kops.dequantize(*kops.quantize(flat[k]), n=flat.shape[1])
                    for k in range(flat.shape[0])]
            return jnp.stack(rows).reshape(x.shape)

        with obs.scope('wire'):
            return jax.tree.map(per_leaf, trained)

    owner = getattr(base_fn, '__self__', None)
    if owner is None:
        return train_fn
    key = getattr(base_fn, '__func__', base_fn)
    cache = owner.__dict__.setdefault('_quantized_train_fns', {})
    if key not in cache:
        cache[key] = train_fn
    return cache[key]


def _capped_round_len(arrival: np.ndarray, mask: np.ndarray,
                      t_lim: float) -> float:
    """Deadline-capped max arrival over ``mask``, ignoring non-finite
    entries; returns ``t_lim`` when nothing finite remains (e.g. every
    client crashed, arrival all inf) so inf never leaks into a
    RoundRecord."""
    live = arrival[mask]
    live = live[np.isfinite(live)]
    return min(t_lim, float(live.max())) if live.size else t_lim


def _sync_round_common(env, selected: np.ndarray, crashed: np.ndarray,
                       cfrac: np.ndarray, t_up: np.ndarray,
                       t_down: np.ndarray, full_tt: np.ndarray):
    """Shared FedAvg/FedCS timing: server waits for every selected client;
    a crash is detected when the client drops (at its partial-progress
    point), so the round ends at max(finish/drop times), capped at T_lim.

    ``t_up``/``t_down``/``full_tt`` are the round's [m] timing rows
    (``Env.round_timing``); with constant traces ``t_down + t_up`` equals
    the legacy ``2 * t_updown`` bitwise."""
    t_dist = env.t_dist(int(selected.sum()))
    finish = t_dist + (t_down + t_up) + full_tt
    drop = t_dist + t_down + cfrac * full_tt
    per_client = np.where(crashed, drop, finish)
    if selected.any():
        round_len = float(np.max(per_client[selected]))
    else:
        round_len = t_dist
    return min(env.t_lim, round_len), t_dist


def _sync_rounds_common(selected, crashed, cfrac, full_tt, *, t_lim,
                        t_up, t_down, msize, server_bw):
    """``_sync_round_common`` vectorised over stacked leading axes.

    selected/crashed/cfrac: [..., m] (e.g. [rounds, m] or [S, rounds, m]);
    the timing arrays must already broadcast against those shapes (for a
    fleet: full_tt/t_up/t_down [S, rounds, m] — or [S, 1, m] when no
    member carries traces — and msize/server_bw/t_lim [S, 1]).
    Bit-identical per round to the scalar helper: the masked max equals
    the compressed max, and every arithmetic expression keeps the scalar
    path's evaluation order ((t_down + t_up) == 2 * t_updown bitwise for
    constant traces).  Returns (round_len [...], t_dist [...])."""
    t_dist = selected.sum(axis=-1) * msize * 8.0 / server_bw
    finish = t_dist[..., None] + (t_down + t_up) + full_tt
    drop = t_dist[..., None] + t_down + cfrac * full_tt
    per_client = np.where(crashed, drop, finish)
    live_max = np.max(np.where(selected, per_client, -np.inf), axis=-1)
    round_len = np.where(selected.any(axis=-1), live_max, t_dist)
    return np.minimum(t_lim, round_len), t_dist


def precompute_sync_schedule(env: FLEnv, *, fraction: float, rounds: int,
                             seed: int, fedcs: bool, form: str = 'dense',
                             sampler: str = 'choice'):
    """Host pass for the synchronous baselines (selection + crash draws).

    ``sampler`` picks the FedAvg selection stream: 'choice' is the legacy
    per-round ``Generator.choice`` draw; 'topk' is the vectorised
    without-replacement sampler (``selection.fedavg_select_topk``) whose
    bulk-uniform stream scales to large m.  FedCS selection is
    deterministic and ignores it.  ``form='sparse'`` emits a
    ``SparseSyncSchedule`` (same loop, compact per-round storage), exactly
    equal to the dense precompute's ``.to_sparse()``."""
    if form not in ('dense', 'sparse'):
        raise ValueError(f"unknown form {form!r} (want 'dense' or 'sparse')")
    m = env.m
    rng = np.random.default_rng(seed + 1)
    tim = env.round_timing(rounds)         # [rounds, m] trace/wire-aware
    work = env.n_batches * env.epochs
    wasted = 0.0
    performed = 0.0
    crashed_all, cfrac_all = env.draw_rounds(rounds)
    sel_idx_all = None
    if not fedcs and sampler == 'topk':
        # one bulk uniform draw for all rounds (row t == round t's draw)
        sel_idx_all = selection.fedavg_select_topk(rng, m, fraction, rounds)
    elif sampler not in ('choice', 'topk'):
        raise ValueError(
            f"unknown sampler {sampler!r} (want 'choice' or 'topk')")
    dense = form == 'dense'
    selected_s = np.zeros((rounds, m), bool) if dense else None
    completed_s = np.zeros((rounds, m), bool) if dense else None
    sparse_rows = []
    records = []

    for t in range(1, rounds + 1):
        t_up, t_down = tim.t_up[t - 1], tim.t_down[t - 1]
        full_tt = tim.full_tt[t - 1]
        if fedcs:
            # per-round estimate: traces move the FedCS pick round to round
            est = (t_down + t_up) + full_tt
            sel = selection.fedcs_select(est, fraction, env.t_lim)
        elif sel_idx_all is not None:
            sel = np.zeros(m, bool)
            sel[sel_idx_all[t - 1]] = True
        else:
            sel = selection.fedavg_select(rng, m, fraction)
        crashed, cfrac = crashed_all[t - 1], cfrac_all[t - 1]
        round_len, t_dist = _sync_round_common(env, sel, crashed, cfrac,
                                               t_up, t_down, full_tt)
        # clients that cannot make the deadline are reckoned crashed (§III-B)
        too_slow = (t_dist + (t_down + t_up) + full_tt) > env.t_lim
        crashed = crashed | too_slow
        completed = sel & ~crashed
        performed += float(np.sum(np.where(sel, np.where(crashed, cfrac, 1.0), 0.0) * work))
        wasted += float(np.sum((sel & crashed) * cfrac * work))

        if dense:
            selected_s[t - 1] = sel
            completed_s[t - 1] = ~crashed
        else:
            sparse_rows.append(schedules.sync_sparse_row(sel, ~crashed))
        records.append(RoundRecord(
            round=t, round_len=round_len, t_dist=t_dist,
            eur=float(completed.sum()) / m,
            sr=float(sel.sum()) / m, vv=0.0,
            n_picked=int(completed.sum()), n_committed=int(completed.sum()),
            n_crashed=int(crashed.sum())))

    futility = wasted / max(performed, 1e-9)
    if not dense:
        idx, roles = schedules.pack_sparse_rows(sparse_rows, m)
        return schedules.SparseSyncSchedule(m=m, idx=idx, roles=roles,
                                            records=records,
                                            futility=futility)
    return SyncSchedule(selected=selected_s, completed=completed_s,
                        records=records, futility=futility)


def precompute_local_schedule(env: FLEnv, *, fraction: float, rounds: int,
                              seed: int) -> LocalSchedule:
    """Host pass for the fully-local baseline (selection + crash draws).

    Consumes the selection rng (``seed + 2``) and the env's crash stream
    exactly as the per-round reference loop does: the two are independent
    generators, so bulk-drawing each preserves both streams."""
    m = env.m
    rng = np.random.default_rng(seed + 2)
    tim = env.round_timing(rounds)         # [rounds, m] trace/wire-aware
    crashed_all, cfrac_all = env.draw_rounds(rounds)
    selected = selection.fedavg_select_batch([rng], m, fraction, rounds)[0]
    completed = selected & ~crashed_all
    round_len, _ = _sync_rounds_common(
        selected, crashed_all, cfrac_all, tim.full_tt, t_lim=env.t_lim,
        t_up=tim.t_up, t_down=tim.t_down, msize=env._dist_mb(),
        server_bw=env.server_bw_mbps)
    round_len = round_len.tolist()
    n_committed = completed.sum(axis=-1).tolist()
    n_crashed = crashed_all.sum(axis=-1).tolist()
    records = [RoundRecord(round=i + 1, round_len=round_len[i], t_dist=0.0,
                           eur=0.0, sr=0.0, vv=0.0, n_picked=0,
                           n_committed=n_committed[i],
                           n_crashed=n_crashed[i])
               for i in range(rounds)]
    return LocalSchedule(completed=completed, records=records, futility=0.0)


def precompute_fedasync_schedule(env: FLEnv, *, rounds: int,
                                 alpha: float = 0.6,
                                 staleness_exp: float = 0.5
                                 ) -> FedasyncSchedule:
    """Run the FedAsync bookkeeping (global-version counter, per-client
    staleness) for all rounds in one host pass, with the crash draws
    vectorised via ``draw_rounds`` (same rng stream as round-by-round
    ``draw_round`` calls)."""
    m = env.m
    tim = env.round_timing(rounds)         # [rounds, m] trace/wire-aware
    crashed_all, _ = env.draw_rounds(rounds)
    # every client syncs every round, so t_dist(m) is round-invariant; the
    # per-client leg varies with the round's traces
    t_dist_m = env.t_dist(m)
    versions = np.zeros(m, dtype=float)   # global version at last pull
    global_version = 0
    committed_s = np.zeros((rounds, m), bool)
    order_s = np.zeros((rounds, m), np.int64)
    alphas_s = np.zeros((rounds, m))
    records = []

    for t in range(1, rounds + 1):
        crashed = crashed_all[t - 1]
        arrival_base = t_dist_m \
            + (tim.t_down[t - 1] + tim.t_up[t - 1]) + tim.full_tt[t - 1]
        arrival = np.where(~crashed, arrival_base, np.inf)
        too_slow = arrival > env.t_lim
        committed = ~crashed & ~too_slow
        staleness = np.maximum(0.0, global_version - versions)
        i = t - 1
        committed_s[i] = committed
        order_s[i] = np.argsort(arrival, kind='stable')
        alphas_s[i] = np.where(
            committed, alpha * (1.0 + staleness) ** (-staleness_exp), 0.0)
        global_version += int(committed.sum())
        versions[committed] = global_version
        records.append(RoundRecord(
            round=t,
            round_len=_capped_round_len(arrival, committed, env.t_lim),
            t_dist=env.t_dist(int(committed.sum())),
            eur=float(committed.sum()) / m,
            sr=1.0,  # every client syncs every round: max downlink pressure
            vv=float(np.var(staleness[committed])) if committed.any() else 0.0,
            n_picked=int(committed.sum()),
            n_committed=int(committed.sum()),
            n_crashed=int(crashed.sum())))

    return FedasyncSchedule(committed=committed_s, order=order_s,
                            alphas=alphas_s, records=records, futility=0.0)


# ---------------------------------------------------------------------------
# Fleet precomputes: batched multi-seed / multi-config sweeps
# ---------------------------------------------------------------------------
#
# A sweep is S independent simulations of the same protocol.  Each member's
# event process is precomputed exactly as for a single run and the resulting
# [rounds, m] schedules stack into [S, rounds, m] tensors — here the whole
# fleet-major state machine runs in one host pass, bit-identical to S
# independent precomputes (regression-tested).

def precompute_fleet_schedule(members, *, rounds: int) -> FleetSchedule:
    """Run S SAFA event state machines in ONE fleet-major host pass.

    Bit-identical to stacking S independent ``precompute_safa_schedule``
    calls (regression-tested): each member's crash/straggler draws come
    from its own env rng, consumed exactly as a standalone precompute
    would, while the version bookkeeping and CFCFM selection run
    vectorised on [S, m] arrays (``selection.cfcfm_batch``).  This is the
    host-side counterpart of the vmapped numeric engine — without it the
    per-member python state machine dominates sweep wall-clock."""
    s_count = len(members)
    envs = [mem.env for mem in members]
    m = envs[0].m
    if any(e.m != m for e in envs):
        raise ValueError('fleet members must share the client count m')
    fraction = np.array([mem.fraction for mem in members], float)
    quota = np.maximum(1, np.rint(fraction * m).astype(int))
    lag = np.array([mem.lag_tolerance for mem in members])[:, None]
    t_lim = np.array([e.t_lim for e in envs])
    msize = np.array([e._dist_mb() for e in envs])
    server_bw = np.array([e.server_bw_mbps for e in envs])
    tims = [e.round_timing(rounds) for e in envs]
    work = np.stack([e.n_batches * e.epochs for e in envs])
    draws = [e.draw_rounds(rounds) for e in envs]
    crashed_all = np.stack([d[0] for d in draws])     # [S, rounds, m]
    cfrac_all = np.stack([d[1] for d in draws])

    v = np.zeros((s_count, m), dtype=int)
    committed_prev = np.ones((s_count, m), bool)
    picked_prev = np.zeros((s_count, m), bool)
    pending = np.zeros((s_count, m))
    wasted = np.zeros(s_count)
    performed = np.zeros(s_count)
    masks = {k: np.zeros((s_count, rounds, m), bool)
             for k in FleetSchedule.MASKS}
    # per-round [S] / [S, m] intermediates; record stats vectorise over
    # rounds after the loop (the loop itself stays O(state-machine) only)
    t_dist_l, quota_met_l, base_v_l = [], [], []

    for t in range(1, rounds + 1):
        gv = t - 1
        staleness = gv - v
        dep = ~committed_prev & (staleness >= lag)
        sync = committed_prev | dep
        wasted += np.sum(np.where(sync, pending * work, 0.0), axis=-1)
        pending = np.where(sync, 0.0, pending)
        v = np.where(sync, gv, v)

        crashed, cfrac = crashed_all[:, t - 1], cfrac_all[:, t - 1]
        remaining = 1.0 - pending
        # per-round [S, m] timing rows (trace/wire-aware; bit-identical to
        # the legacy t_updown * (1 + sync) algebra under constant traces)
        t_up_r = np.stack([tt.t_up[t - 1] for tt in tims])
        t_down_r = np.stack([tt.t_down[t - 1] for tt in tims])
        t_train = remaining * np.stack([tt.full_tt[t - 1] for tt in tims])
        t_dist = sync.sum(axis=-1) * msize * 8.0 / server_bw
        arrival = t_dist[:, None] + (t_up_r + sync * t_down_r) \
            + t_train
        completed = ~crashed
        arrival = np.where(completed, arrival, np.inf)
        performed += np.sum(np.where(completed, remaining,
                                     cfrac * remaining) * work, axis=-1)
        base_versions = v.copy()

        sel = selection.cfcfm_batch(arrival, completed, picked_prev,
                                    fraction, t_lim, quota=quota)
        pending = np.where(crashed,
                           np.minimum(pending + cfrac * remaining, 0.999),
                           pending)
        pending = np.where(sel.committed, 0.0, pending)
        v = np.where(sel.committed, t, v)

        i = t - 1
        masks['sync'][:, i] = sync
        masks['committed'][:, i] = sel.committed
        masks['picked'][:, i] = sel.picked
        masks['undrafted'][:, i] = sel.undrafted
        masks['deprecated'][:, i] = dep
        t_dist_l.append(t_dist)
        quota_met_l.append(sel.quota_met_time)
        base_v_l.append(base_versions)
        committed_prev = sel.committed
        picked_prev = sel.picked

    # bulk-convert stat tensors to python scalars once (.tolist()) rather
    # than casting S*rounds*9 numpy scalars one by one
    t_dist_a = np.stack(t_dist_l, axis=1).tolist()            # [S][rounds]
    round_len = np.minimum(t_lim[:, None],
                           np.stack(quota_met_l, axis=1)).tolist()
    n_picked = masks['picked'].sum(axis=-1).tolist()
    n_committed = masks['committed'].sum(axis=-1).tolist()
    n_crashed = crashed_all.sum(axis=-1).tolist()
    n_sync = masks['sync'].sum(axis=-1).tolist()
    vv = _masked_var(np.stack(base_v_l, axis=1),
                     masks['committed']).tolist()
    records = [[RoundRecord(
        round=i + 1,
        round_len=round_len[s][i],
        t_dist=t_dist_a[s][i],
        eur=n_picked[s][i] / m,
        sr=n_sync[s][i] / m,
        vv=vv[s][i],
        n_picked=n_picked[s][i],
        n_committed=n_committed[s][i],
        n_crashed=n_crashed[s][i],
    ) for i in range(rounds)] for s in range(s_count)]
    return FleetSchedule(records=records,
                         futility=wasted / np.maximum(performed, 1e-9),
                         **masks)


def precompute_sync_fleet_schedule(members, *, rounds: int, fedcs: bool,
                                   sampler: str = 'choice'
                                   ) -> SyncFleetSchedule:
    """FedAvg/FedCS host pass for a whole fleet in one [S, rounds, m] sweep.

    Bit-identical to stacking S ``precompute_sync_schedule`` calls
    (regression-tested) with the per-member Python state loop eliminated:
    FedCS selection is one ``selection.fedcs_select_batch`` rank
    comparison (when no member carries traces the time estimates are
    round-invariant and one [S, m] selection broadcasts over rounds; with
    traces the rounds axis folds into the batch axis — one
    [S*rounds, m] call), FedAvg selections consume each
    member's own rng stream (``selection.fedavg_select_batch``), and the
    timing/crash algebra plus record stats vectorise over the full
    [S, rounds, m] block.  Synchronous protocols carry no cross-round
    state, so there is no per-round loop either — the futility
    accumulators use ``np.cumsum`` to keep the scalar path's sequential
    round-by-round addition order."""
    s_count = len(members)
    envs = [mem.env for mem in members]
    m = envs[0].m
    if any(e.m != m for e in envs):
        raise ValueError('fleet members must share the client count m')
    fraction = np.array([mem.fraction for mem in members], float)
    t_lim = np.array([e.t_lim for e in envs])
    msize = np.array([e._dist_mb() for e in envs])
    server_bw = np.array([e.server_bw_mbps for e in envs])
    work = np.stack([e.n_batches * e.epochs for e in envs])     # [S, m]
    draws = [e.draw_rounds(rounds) for e in envs]
    crashed_all = np.stack([d[0] for d in draws])               # [S, rounds, m]
    cfrac_all = np.stack([d[1] for d in draws])

    tims = [e.round_timing(rounds) for e in envs]
    if any(e.has_traces for e in envs):
        # time-varying timing: full [S, rounds, m] stacks, and FedCS picks
        # per round (estimates move round to round)
        t_up = np.stack([tt.t_up for tt in tims])
        t_down = np.stack([tt.t_down for tt in tims])
        full_tt = np.stack([tt.full_tt for tt in tims])
        if fedcs:
            est = ((t_down + t_up) + full_tt).reshape(s_count * rounds, m)
            sel = selection.fedcs_select_batch(
                est, np.repeat(fraction, rounds), np.repeat(t_lim, rounds))
            selected = sel.reshape(s_count, rounds, m)
    else:
        # round-invariant timing: [S, 1, m] row-0 views broadcast over
        # rounds (legacy memory shape), one FedCS selection for all rounds
        t_up = np.stack([tt.t_up[0] for tt in tims])[:, None]
        t_down = np.stack([tt.t_down[0] for tt in tims])[:, None]
        full_tt = np.stack([tt.full_tt[0] for tt in tims])[:, None]
        if fedcs:
            est = (t_down[:, 0] + t_up[:, 0]) + full_tt[:, 0]   # [S, m]
            sel = selection.fedcs_select_batch(est, fraction, t_lim)
            selected = np.broadcast_to(sel[:, None],
                                       (s_count, rounds, m)).copy()
    if not fedcs:
        rngs = [np.random.default_rng(mem.seed + 1) for mem in members]
        selected = selection.fedavg_select_batch(rngs, m, fraction, rounds,
                                                 sampler=sampler)

    round_len, t_dist = _sync_rounds_common(
        selected, crashed_all, cfrac_all, full_tt,
        t_lim=t_lim[:, None], t_up=t_up, t_down=t_down,
        msize=msize[:, None], server_bw=server_bw[:, None])
    # clients that cannot make the deadline are reckoned crashed (§III-B)
    too_slow = (t_dist[..., None] + (t_down + t_up)
                + full_tt) > t_lim[:, None, None]
    crashed = crashed_all | too_slow
    completed = selected & ~crashed
    performed = np.sum(np.where(selected, np.where(crashed, cfrac_all, 1.0),
                                0.0) * work[:, None], axis=-1)  # [S, rounds]
    wasted = np.sum((selected & crashed) * cfrac_all * work[:, None], axis=-1)
    performed_tot = np.cumsum(performed, axis=1)[:, -1]
    wasted_tot = np.cumsum(wasted, axis=1)[:, -1]

    round_len_l = round_len.tolist()
    t_dist_l = t_dist.tolist()
    n_completed = completed.sum(axis=-1).tolist()
    n_sel = selected.sum(axis=-1).tolist()
    n_crashed = crashed.sum(axis=-1).tolist()
    records = [[RoundRecord(
        round=i + 1, round_len=round_len_l[s][i], t_dist=t_dist_l[s][i],
        eur=n_completed[s][i] / m,
        sr=n_sel[s][i] / m, vv=0.0,
        n_picked=n_completed[s][i], n_committed=n_completed[s][i],
        n_crashed=n_crashed[s][i],
    ) for i in range(rounds)] for s in range(s_count)]
    return SyncFleetSchedule(
        selected=selected, completed=~crashed, records=records,
        futility=wasted_tot / np.maximum(performed_tot, 1e-9))


# ---------------------------------------------------------------------------
# Legacy runner shims (DeprecationWarning; bit-identical to the spec path)
# ---------------------------------------------------------------------------

def _deprecated(name: str, spelling: str):
    # attribute the warning to the first frame OUTSIDE this module, so
    # run_fedcs -> run_fedavg chains still point at the user's call site
    # (and per-call-site warning dedup keeps working)
    level, frame = 3, sys._getframe(2)
    while frame is not None and frame.f_globals.get('__name__') == __name__:
        level += 1
        frame = frame.f_back
    warnings.warn(
        f'federation.{name}() is deprecated; spell it as {spelling} '
        f'(repro.api — see docs/ARCHITECTURE.md, "The API layer")',
        DeprecationWarning, stacklevel=level)


def run_safa(task: Optional[Task], env: FLEnv, *, fraction: float,
             lag_tolerance: int, rounds: int, eval_every: int = 10,
             numeric: bool = True, use_kernel=False,
             quantize_uploads: bool = False, seed: int = 0,
             engine: str = 'scan', wire: str = 'f32') -> History:
    """Deprecated shim over ``api.Experiment(..., SafaSpec(...))``.

    ``wire='int8'`` runs every round on the compressed-wire fast path
    (packed int8 uplink + fused dequant-aggregate kernel, 2 dispatches per
    round); ``quantize_uploads=True`` is the per-leaf reference form of
    the same wire (2 dispatches per leaf per client), kept as the
    bit-identity ground truth — the two are mutually exclusive."""
    _deprecated('run_safa', 'Experiment(task, env, SafaSpec(...), '
                'ExecSpec(...)).compile().run()')
    from repro.core import api
    exp = api.Experiment(
        task, env,
        api.SafaSpec(fraction=fraction, lag_tolerance=lag_tolerance,
                     quantize_uploads=quantize_uploads),
        api.ExecSpec(engine=engine, wire=wire, use_kernel=use_kernel,
                     eval_every=eval_every, numeric=numeric),
        rounds=rounds, seed=seed)
    return exp.compile().run()


def run_fedavg(task: Optional[Task], env: FLEnv, *, fraction: float,
               rounds: int, eval_every: int = 10, numeric: bool = True,
               seed: int = 0, fedcs: bool = False,
               engine: str = 'scan', wire: str = 'f32') -> History:
    """Deprecated shim over ``api.Experiment(..., FedAvgSpec/FedCSSpec)``.

    ``wire='int8'`` ships the uploads through the packed int8 wire
    (cross-protocol comparison against SAFA's compressed fast path)."""
    _deprecated('run_fedcs' if fedcs else 'run_fedavg',
                'Experiment(task, env, FedCSSpec(...) if fedcs else '
                'FedAvgSpec(...), ExecSpec(...)).compile().run()')
    from repro.core import api
    spec_cls = api.FedCSSpec if fedcs else api.FedAvgSpec
    exp = api.Experiment(
        task, env, spec_cls(fraction=fraction),
        api.ExecSpec(engine=engine, wire=wire, eval_every=eval_every,
                     numeric=numeric),
        rounds=rounds, seed=seed)
    return exp.compile().run()


def run_fedcs(task, env, **kw) -> History:
    return run_fedavg(task, env, fedcs=True, **kw)


def run_local(task: Optional[Task], env: FLEnv, *, fraction: float,
              rounds: int, eval_every: int = 10, numeric: bool = True,
              seed: int = 0, engine: str = 'scan', wire: str = 'f32',
              use_kernel=False) -> History:
    """Deprecated shim over ``api.Experiment(..., LocalSpec(...))``.

    Fully-local baseline: C-fraction of clients train each round with no
    aggregation; a weighted aggregation happens at eval points (and after
    the last round) only.  ``wire``/``use_kernel`` are accepted for
    signature parity and rejected by ``api.check_compat`` with the same
    message every surface uses."""
    _deprecated('run_local', 'Experiment(task, env, LocalSpec(...), '
                'ExecSpec(...)).compile().run()')
    from repro.core import api
    exp = api.Experiment(
        task, env, api.LocalSpec(fraction=fraction),
        api.ExecSpec(engine=engine, wire=wire, use_kernel=use_kernel,
                     eval_every=eval_every, numeric=numeric),
        rounds=rounds, seed=seed)
    return exp.compile().run()


def run_fedasync(task: Optional[Task], env: FLEnv, *, fraction: float = 1.0,
                 rounds: int = 100, eval_every: int = 10,
                 numeric: bool = True, alpha: float = 0.6,
                 staleness_exp: float = 0.5, seed: int = 0,
                 engine: str = 'scan', wire: str = 'f32',
                 use_kernel=False) -> History:
    """Deprecated shim over ``api.Experiment(..., FedAsyncSpec(...))``.

    FedAsync baseline (Xie et al. [9], paper §II): every willing client
    trains every round and the server merges each arriving update
    immediately with staleness-polynomial mixing
    alpha_eff = alpha * (1 + staleness)^(-staleness_exp).  ``fraction`` is
    ignored (fully asynchronous); ``wire``/``use_kernel`` are rejected by
    ``api.check_compat`` with the same message every surface uses."""
    del fraction
    _deprecated('run_fedasync', 'Experiment(task, env, FedAsyncSpec(...), '
                'ExecSpec(...)).compile().run()')
    from repro.core import api
    exp = api.Experiment(
        task, env, api.FedAsyncSpec(alpha=alpha, staleness_exp=staleness_exp),
        api.ExecSpec(engine=engine, wire=wire, use_kernel=use_kernel,
                     eval_every=eval_every, numeric=numeric),
        rounds=rounds, seed=seed)
    return exp.compile().run()


def run_sweep(task, members, *, rounds: int,
              proto: str = 'safa', eval_every: int = 10,
              numeric: bool = True, use_kernel=False,
              engine: str = 'fleet', shard: bool = True,
              wire: str = 'f32') -> list:
    """Deprecated shim over ``api.CompiledRunner.run_sweep``.

    Runs S = len(members) simulations of one protocol as a batched fleet
    and returns one ``History`` per member, in order.  ``task`` may also
    be a *list* of per-member Tasks (one per member, padded stacking) —
    the ``api.SweepSpec(members, tasks=...)`` spelling.

    ``use_kernel`` keeps its historical leniency: it only applies when
    ``proto == 'safa'`` and is silently ignored otherwise (the api path
    rejects it instead)."""
    _deprecated('run_sweep', 'Experiment(task, env, spec, ExecSpec(...))'
                '.compile().run_sweep(members)')
    from repro.core import api
    protocol_spec = api.spec(proto)
    if isinstance(task, (list, tuple)):
        sweep = api.SweepSpec(members=tuple(members), tasks=tuple(task))
        task = None
    else:
        sweep = list(members)
    exp = api.Experiment(
        task, members[0].env if members else None, protocol_spec,
        api.ExecSpec(engine=engine, wire=wire,
                     use_kernel=use_kernel if proto == 'safa' else False,
                     shard=shard, eval_every=eval_every, numeric=numeric),
        rounds=rounds)
    return exp.compile().run_sweep(sweep)


RUNNERS = {
    'safa': run_safa,
    'fedavg': run_fedavg,
    'fedcs': run_fedcs,
    'local': run_local,
    'fedasync': run_fedasync,
}

# Backwards-compatible alias (pre-unification name).  NOTE: the *new*
# registry keyed by spec type lives in ``repro.api.PROTOCOLS``.
PROTOCOLS = RUNNERS
