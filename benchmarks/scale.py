"""Quota-bounded scale benchmark: million-client populations on one host.

    PYTHONPATH=src python -m benchmarks.scale [--smoke] [--xl] [--json F]

The tentpole claim of the sparse active-set schedules: at a *fixed
absolute quota* (``--quota``, default 50 clients/round), per-round
compiled cost and resident memory are functions of the quota, not of the
population size m.  This script sweeps m over decades while holding the
quota constant and reports, per (protocol, schedule) cell:

  * ``rounds_per_sec``  — the steady-state rate of the compiled scan
    engine: one warm full-segment dispatch on device-resident state, so
    per-run O(m) setup (state init, weights transfer) and host schedule
    precompute are excluded (the latter is reported as ``precompute_s``,
    the run-level rate including setup as ``rounds_per_sec_total``);
  * ``sched_mb`` / ``state_mb`` — deterministic nbytes accounting of the
    [rounds, K] event tensors and the device-resident model state;
  * ``vm_hwm_mb`` — the kernel's peak-RSS high-water mark.  In the default
    mode every cell runs in its own subprocess so the figure is an honest
    per-cell peak; under ``--smoke``/``--inproc`` cells share the process
    and the column is monotone (still an upper bound per cell).

Acceptance regime (see ISSUE/ROADMAP): ``rounds_per_sec`` flat within
~20% across m in {1e3, 1e4, 1e5}; ``--xl`` adds the m=1e6 cells — FedAvg
``sparse_delta`` (stateless O(d) carry) and SAFA ``sparse_tier`` (the
lag-tier value buffer: O((tau+quota)·d) resident state, so SAFA's
stateful protocol also runs at a million clients on one host).

``--guard`` is the CI memory-regression gate: it runs the m=1e5 SAFA
``sparse_tier`` cell in its own subprocess and fails if its per-cell
``vm_hwm_mb`` exceeds ``TIER_HWM_BUDGET_MB``.

The environment is tuned so the active set stays O(quota) as m grows:
``lag_tolerance >= rounds`` (no mass forced-sync of stale clients) and
``t_lim`` pinned to the ~2.5*quota-th fastest client's round time, so the
number of *completing* clients per round — which bounds SAFA's active set
— is quota-bounded by construction rather than O(m).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

QUOTA = 50          # fixed absolute quota (clients aggregated per round)
ROUNDS = 40
SMOKE_M = 10_000
M_GRID = (1_000, 10_000, 100_000)
XL_M = 1_000_000
D = 64              # model dimension (per-client state is D floats)

# (protocol, schedule) cells; ``max_m`` gates cells whose resident state
# is O(m * D) — at m=1e6 only the stateless fedavg delta engine runs.
CELLS = (
    ('fedavg', 'dense', 10_000),
    ('safa', 'dense', 10_000),
    ('fedavg', 'sparse', 100_000),
    ('safa', 'sparse', 100_000),
    ('fedavg', 'sparse_delta', None),       # stateless: O(D) carry
    ('safa', 'sparse_delta', 100_000),
    ('safa', 'sparse_tier', None),          # lag-tier: O((tau+quota)*D)
)

#: committed per-cell peak-RSS budget for the m=1e5 SAFA sparse_tier cell
#: (``--guard``).  The cell's honest subprocess HWM is dominated by the
#: jax/XLA runtime plus the O(m) host event machine; a reintroduced
#: [m, D] device stack at m=1e5 adds ~25 MB per copy and the engines keep
#: several live, so the budget is set with ~2.5x headroom over the
#: measured ~205 MB — tight enough that an O(m·D) state regression trips.
TIER_HWM_BUDGET_MB = 512.0


class ScaleTask:
    """Minimal rows-contract task with *index-derived* data: client k's
    target is a deterministic function of k, so the task itself holds no
    [m, ...] tensors and memory scales only with the model and the active
    set.  The train step is an elementwise pull toward the target, which
    makes ``local_train_rows`` trivially bit-identical to ``local_train``
    (the sparse==dense contract)."""

    def __init__(self, d: int = D, lr: float = 0.3):
        self.d, self.lr = d, lr

    def _targets(self, rows):
        import jax.numpy as jnp
        k = rows[:, None].astype(jnp.float32)
        j = jnp.arange(self.d, dtype=jnp.float32)[None, :]
        return jnp.sin(k * 0.7 + j * 0.13)

    def init_global(self, key):
        import jax
        return {'w': 0.01 * jax.random.normal(key, (self.d,),
                                              dtype='float32')}

    def local_train(self, stacked_params, round_idx):
        import jax.numpy as jnp
        m = stacked_params['w'].shape[0]
        rows = jnp.arange(m, dtype=jnp.int32)
        return self.local_train_rows(stacked_params, rows, round_idx)

    def local_train_rows(self, params_rows, rows, round_idx):  # noqa: ARG002
        p = params_rows['w']
        return {'w': p + self.lr * (self._targets(rows) - p)}

    def evaluate(self, global_params) -> dict:
        import jax.numpy as jnp
        t = self._targets(jnp.arange(256, dtype=jnp.int32))
        return {'loss': float(jnp.mean(
            (global_params['w'][None, :] - t) ** 2))}


def make_scale_env(m: int, quota: int, seed: int = 0, *,
                   bound_active: bool = True):
    """Environment for the quota-bounded regime.

    ``bound_active=True`` (SAFA) pins ``t_lim`` at the ~2.5*quota-th
    fastest client's training time, so the number of *completing* clients
    per round — which bounds SAFA's active set (committed + undrafted,
    plus last round's committed as sync) — is ~2.5*quota at every m.
    Communication terms are made negligible (``model_size_mb``) so the
    sync/non-sync arrival asymmetry cannot reopen the deadline to O(m)
    completions.  ``bound_active=False`` (FedAvg/FedCS, whose active set
    is the selection quota by construction) keeps a permissive deadline
    so selected clients actually complete."""
    from repro.fedsim import EnvSpec
    # crash_prob=0: a crashed straggler carries partial progress and can
    # slip under next round's deadline, so at crash_prob>0 the completing
    # population grows as O(crash_prob * m) — a protocol-faithful effect,
    # but this benchmark isolates the quota-bounded server path.
    spec = EnvSpec(m=m, crash_prob=0.0, dataset_size=20 * m, batch_size=10,
                   epochs=1, t_lim=1e9, seed=seed, model_size_mb=1e-3)
    env = spec.build()
    if not bound_active:
        return env
    base = env.t_updown + env.full_train_time()
    k = min(m - 1, int(round(2.5 * quota)))
    t_lim = float(np.partition(base, k)[k])
    return spec.replace(t_lim=t_lim).build()


def _vm_mb(field: str) -> float:
    try:
        with open('/proc/self/status') as f:
            for line in f:
                if line.startswith(field + ':'):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return float('nan')


def _tree_nbytes(tree) -> int:
    import jax
    return sum(getattr(l, 'nbytes', 0)
               for l in jax.tree_util.tree_leaves(tree))


def _build(protocol: str, schedule: str, m: int, quota: int, rounds: int,
           seed: int):
    from repro import api
    env = make_scale_env(m, quota, seed=seed,
                         bound_active=(protocol == 'safa'))
    proto_kw = {'fraction': quota / m}
    if protocol == 'safa':
        # > any round count used here: no mass forced-sync of stale clients
        proto_kw['lag_tolerance'] = 10 * rounds
    if protocol == 'fedavg':
        proto_kw['sampler'] = 'topk'             # O(m) vectorised draw
    return api.Experiment(
        ScaleTask(), env, api.spec(protocol, **proto_kw),
        api.ExecSpec(engine='scan', schedule=schedule, eval_every=rounds),
        rounds=rounds, seed=seed)


def _timed_run(runner, reps: int = 3) -> float:
    """Best-of-``reps`` wall time of a fully warm ``run()``."""
    best = float('inf')
    for _ in range(reps):
        t0 = time.perf_counter()
        runner.run()
        best = min(best, time.perf_counter() - t0)
    return best


def _timed_segment(runner, reps: int = 5):
    """Best-of-``reps`` wall time of one warm full-segment scan dispatch
    on device-resident state — the steady-state compiled round, with no
    per-run O(m) setup in the measurement window.  The scan engines
    donate their carry, so repeated dispatches chain on the same state
    exactly as consecutive eval segments do in ``run()``.  Returns
    ``(seconds, state_nbytes)``; the state-bytes figure is taken from
    the same prepared state the timing uses."""
    import jax
    import jax.numpy as jnp
    from repro.core import api as _api
    exp = runner.exp
    ex = exp.exec
    st = _api._init_state(exp.task, exp.env.m, exp.seed,
                          runner._pdef.uses_cache, runner._stateless(ex))
    weights_j = jnp.asarray(exp.env.weights)
    if runner._pdef.prepare_state is not None:
        runner._pdef.prepare_state(st, weights_j, ex, False, exp.precompute())
    state_b = _tree_nbytes(st.tree())
    train_fn = runner._train_fn(exp.task)
    seg = jax.tree.map(lambda a: a[0:exp.rounds], runner._dev)

    def segment():
        _api._run_segment(runner._form, 'scan', st, seg, weights_j,
                          train_fn, ex)

    segment()
    jax.block_until_ready(st.global_w)
    best = float('inf')
    for _ in range(reps):
        t0 = time.perf_counter()
        segment()
        jax.block_until_ready(st.global_w)
        best = min(best, time.perf_counter() - t0)
    return best, state_b


def run_cell(protocol: str, schedule: str, m: int, *, quota: int = QUOTA,
             rounds: int = ROUNDS, seed: int = 0) -> dict:
    """One (protocol, schedule, m) measurement; returns a result dict.

    ``rounds_per_sec`` times the compiled full-segment scan dispatch on
    warm device-resident state (``_timed_segment``) — the steady-state
    per-round cost the quota-bounded claim is about.  Per-run O(m) setup
    (state init, weights transfer) is excluded there and shows up in
    ``rounds_per_sec_total``, the plain R/wall rate of a full ``run()``;
    ``precompute_s`` is the host schedule build."""
    exp = _build(protocol, schedule, m, quota, rounds, seed)

    t0 = time.perf_counter()
    sched = exp.precompute()
    pre_s = time.perf_counter() - t0
    runner = exp.compile()
    hist = runner.run()                      # compile + warm; loss sanity
    t_total = _timed_run(runner)
    t_seg, state_b = _timed_segment(runner)

    sched_b = getattr(sched, 'nbytes', None) or _tree_nbytes(
        sched.__dict__ if hasattr(sched, '__dict__') else sched)
    return {
        'protocol': protocol, 'schedule': schedule, 'm': m,
        'quota': quota, 'rounds': rounds,
        'capacity': getattr(sched, 'capacity', m),
        'rounds_per_sec': rounds / t_seg,
        'rounds_per_sec_total': rounds / t_total,
        'precompute_s': pre_s,
        'sched_mb': sched_b / 1e6,
        'state_mb': state_b / 1e6,
        'vm_hwm_mb': _vm_mb('VmHWM'),
        'vm_rss_mb': _vm_mb('VmRSS'),
        'loss': hist.best_eval['loss'],
    }


def _cell_subprocess(protocol, schedule, m, quota, rounds, *,
                     platform: str | None = None) -> dict:
    """Run one cell in a child interpreter so VmHWM is a per-cell peak.
    ``platform`` pins the child's JAX platform (JAX_PLATFORMS)."""
    cmd = [sys.executable, '-m', 'benchmarks.scale', '--cell',
           f'{protocol}:{schedule}:{m}', '--quota', str(quota),
           '--rounds', str(rounds)]
    env = dict(os.environ)
    if platform is not None:
        env['JAX_PLATFORMS'] = platform
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env['PYTHONPATH'] = os.pathsep.join(
        p for p in (os.path.join(root, 'src'), root,
                    env.get('PYTHONPATH', '')) if p)
    out = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(f'cell {protocol}:{schedule}:{m} failed:\n'
                           f'{out.stderr[-2000:]}')
    return json.loads(out.stdout.strip().splitlines()[-1])


def collect(ms, *, quota: int = QUOTA, rounds: int = ROUNDS,
            inproc: bool = False, xl: bool = False, echo=print) -> list:
    """All (cell, m) measurements; echoes one CSV row per result."""
    results = []
    jobs = [(p, s, m) for m in ms for (p, s, max_m) in CELLS
            if max_m is None or m <= max_m]
    if xl:
        jobs += [('fedavg', 'sparse_delta', XL_M),
                 ('safa', 'sparse_tier', XL_M)]
    for p, s, m in jobs:
        r = (run_cell(p, s, m, quota=quota, rounds=rounds) if inproc
             else _cell_subprocess(p, s, m, quota, rounds))
        results.append(r)
        echo(f'scale/{p}/{s}/m={m},{r["rounds_per_sec"]:.2f},'
             f'rounds_per_sec '
             f'(K={r["capacity"]} sched={r["sched_mb"]:.2f}MB '
             f'state={r["state_mb"]:.1f}MB hwm={r["vm_hwm_mb"]:.0f}MB '
             f'pre={r["precompute_s"]:.2f}s)')
    return results


def run(*, smoke: bool = False, xl: bool = False, quota: int = QUOTA,
        rounds: int = ROUNDS, json_path: str | None = None) -> list:
    """Entry point used by ``benchmarks.run``: smoke runs a single
    in-process m so CI stays fast; full runs the decade sweep with
    per-cell subprocesses for honest peak-RSS."""
    ms = (SMOKE_M,) if smoke else M_GRID
    rounds = 8 if smoke else rounds
    results = collect(ms, quota=quota, rounds=rounds,
                      inproc=smoke, xl=xl and not smoke)
    if json_path:
        with open(json_path, 'w') as f:
            json.dump({'quota': quota, 'rounds': rounds,
                       'cells': results}, f, indent=1)
        print(f'# wrote {json_path}', flush=True)
    return results


def guard(*, budget_mb: float = TIER_HWM_BUDGET_MB, quota: int = QUOTA,
          rounds: int = ROUNDS) -> dict:
    """CI memory-regression gate: the m=1e5 SAFA ``sparse_tier`` cell in
    its own subprocess (honest per-cell VmHWM) against the committed
    budget.  Raises ``SystemExit`` on regression.  VmHWM is host memory,
    so the child runs on the CPU and never touches an accelerator."""
    r = _cell_subprocess('safa', 'sparse_tier', 100_000, quota, rounds,
                         platform='cpu')
    hwm = r['vm_hwm_mb']
    print(f'scale-guard/safa/sparse_tier/m=100000,{hwm:.0f},'
          f'vm_hwm_mb (budget {budget_mb:.0f}MB)', flush=True)
    if not hwm <= budget_mb:
        raise SystemExit(
            f'memory regression: m=1e5 safa sparse_tier VmHWM '
            f'{hwm:.0f} MB exceeds the committed budget {budget_mb:.0f} MB '
            f'(benchmarks/scale.py TIER_HWM_BUDGET_MB)')
    return r


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--smoke', action='store_true',
                    help=f'single in-process m={SMOKE_M} pass (CI guard)')
    ap.add_argument('--xl', action='store_true',
                    help=f'add the m={XL_M} fedavg sparse_delta and '
                         f'safa sparse_tier cells')
    ap.add_argument('--guard', action='store_true',
                    help='memory-regression gate: fail if the m=1e5 safa '
                         'sparse_tier cell peaks above '
                         f'{TIER_HWM_BUDGET_MB:.0f} MB RSS')
    ap.add_argument('--inproc', action='store_true',
                    help='no per-cell subprocesses (VmHWM then monotone)')
    ap.add_argument('--quota', type=int, default=QUOTA)
    ap.add_argument('--rounds', type=int, default=ROUNDS)
    ap.add_argument('--json', default=None, metavar='FILE')
    ap.add_argument('--cell', default=None, metavar='P:S:M',
                    help='internal: run one cell, print its JSON')
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.cell:
        p, s, m = args.cell.split(':')
        print(json.dumps(run_cell(p, s, int(m), quota=args.quota,
                                  rounds=args.rounds)))
        return
    print('name,us_per_call,derived')
    if args.guard:
        guard(quota=args.quota, rounds=args.rounds)
        return
    if args.smoke:
        run(smoke=True, quota=args.quota, json_path=args.json)
    else:
        results = collect(M_GRID, quota=args.quota, rounds=args.rounds,
                          inproc=args.inproc, xl=args.xl)
        if args.json:
            with open(args.json, 'w') as f:
                json.dump({'quota': args.quota, 'rounds': args.rounds,
                           'cells': results}, f, indent=1)
            print(f'# wrote {args.json}', flush=True)


if __name__ == '__main__':
    main()
