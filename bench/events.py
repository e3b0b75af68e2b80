"""SAFA's event process, written from the paper, for the plain reference.

Given the traffic an environment drew -- per-round training, upload and
download times, crash draws and the deadline -- this replays Eq. 3's
version bookkeeping (lag tolerance, forced sync of deprecated clients),
the straggler progress that a crash carries into the next round, and
CFCFM selection (compensatory first-come-first-merge: clients not picked
last round first, in arrival order, up to the quota; then the rest).  It
imports nothing of the program, so a change to the program's own event
precompute is checked against it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Draws:
    """What the environment drew, as plain arrays ([rounds, m] or [m])."""
    t_up: np.ndarray            # upload seconds
    t_down: np.ndarray          # download seconds
    full_tt: np.ndarray         # full local training seconds
    crashed: np.ndarray         # bool
    crash_frac: np.ndarray      # share of the round's work done before a crash
    weights: np.ndarray         # [m] aggregation weights n_k / n
    t_lim: float                # round deadline
    dist_mb: float              # megabytes the server ships per copy
    server_bw_mbps: float


@dataclasses.dataclass(frozen=True)
class Masks:
    """[rounds, m] bool role masks of each round."""
    sync: np.ndarray
    committed: np.ndarray
    picked: np.ndarray
    undrafted: np.ndarray
    deprecated: np.ndarray

    def rows_read(self) -> np.ndarray:
        """[rounds] clients whose own local model a round must read: those
        that commit without having synced (a synced client starts from
        the global)."""
        return (self.committed & ~self.sync).sum(axis=1)

    def uploads(self) -> np.ndarray:
        """[rounds] clients whose upload is aggregated (they commit)."""
        return self.committed.sum(axis=1)

    def rows_written(self) -> np.ndarray:
        """[rounds] cache entries a round changes (Eq. 6 and 8)."""
        return (self.picked | self.undrafted
                | (self.deprecated & ~self.picked)).sum(axis=1)

    def active(self) -> np.ndarray:
        """[m] clients that take a part in some round: they commit, are
        picked, undrafted or deprecated, or sync after the first round
        (in the first, every client syncs to the initial model it
        already holds).  The others hold the initial model as local
        model and cache entry throughout."""
        part = self.committed | self.picked | self.undrafted | self.deprecated
        part[1:] |= self.sync[1:]
        return part.any(axis=0)

    def take(self, clients) -> 'Masks':
        """The masks of ``clients`` alone."""
        return Masks(**{k: v[:, clients] for k, v in vars(self).items()})


def _first_by_arrival(eligible, arrival, n):
    """Indices of the first ``n`` eligible clients by arrival time (ties
    by client index)."""
    idx = np.flatnonzero(eligible)
    return idx[np.argsort(arrival[idx], kind='stable')][:max(n, 0)]


def safa_masks(d: Draws, *, fraction: float, lag_tolerance: int,
               rounds: int) -> Masks:
    m = d.weights.shape[0]
    quota = max(1, int(round(fraction * m)))
    version = np.zeros(m, dtype=int)
    committed_prev = np.ones(m, bool)       # round 1: everyone holds w(0)
    picked_prev = np.zeros(m, bool)
    pending = np.zeros(m)                   # work done before a crash
    out = {k: np.zeros((rounds, m), bool) for k in
           ('sync', 'committed', 'picked', 'undrafted', 'deprecated')}
    for t in range(1, rounds + 1):
        i = t - 1
        # Eq. 3: up to date (committed last round) or deprecated (lag at
        # least tau) clients take the latest global model
        deprecated = ~committed_prev & (t - 1 - version >= lag_tolerance)
        sync = committed_prev | deprecated
        pending[sync] = 0.0
        version[sync] = t - 1
        remaining = 1.0 - pending
        t_dist = int(sync.sum()) * d.dist_mb * 8.0 / d.server_bw_mbps
        arrival = t_dist + (d.t_up[i] + sync * d.t_down[i]) \
            + remaining * d.full_tt[i]
        crashed = d.crashed[i]
        arrival = np.where(crashed, np.inf, arrival)
        committed = ~crashed & (arrival <= d.t_lim)
        picked = np.zeros(m, bool)
        picked[_first_by_arrival(committed & ~picked_prev, arrival,
                                 quota)] = True
        picked[_first_by_arrival(committed & ~picked, arrival,
                                 quota - int(picked.sum()))] = True
        pending = np.where(crashed, np.minimum(
            pending + d.crash_frac[i] * remaining, 0.999), pending)
        pending[committed] = 0.0
        version[committed] = t
        for k, v in (('sync', sync), ('committed', committed),
                     ('picked', picked), ('undrafted', committed & ~picked),
                     ('deprecated', deprecated)):
            out[k][i] = v
        committed_prev, picked_prev = committed, picked
    return Masks(**out)
