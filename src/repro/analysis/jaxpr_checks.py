"""Jaxpr contract checker: lower every registered engine cell and prove
the compiled-program invariants the benchmarks and kernels rely on.

For each spec in ``api.PROTOCOLS`` this pass enumerates every
``engine x wire x schedule x use_kernel`` cell that ``check_compat``
admits, builds the exact segment program ``CompiledRunner`` dispatches
(tiny shapes: m=5 regression task, 2-round segments), traces it with
``jax.make_jaxpr`` — no execution — and walks the closed jaxpr:

* **JAX001** — pallas dispatch budget: the dispatch count per round
  equals ``ProtocolDef.dispatch_budget(ex)``.  The PR 4 "a fully
  compressed SAFA round is exactly 2 dispatches" invariant is data on
  the registration, not a one-off benchmark assert; cells with no
  declared budget (e.g. the leaf-wise path, whose count scales with the
  model's leaf count) report the measured count informationally.
* **JAX002** — donations take effect: every ``jit`` call that donates
  arguments must expose, for each donated input buffer, a distinct
  output with the same shape/dtype — otherwise XLA silently drops the
  donation and the engine pays a hidden model-sized copy per segment.
* **JAX003** — alias claims: every ``input_output_aliases`` claim the
  cell's registration makes (``ProtocolDef.alias_claims(ex)``) appears
  in the lowered module, and *every* pallas_call found anywhere in the
  program matches its kernel module's ``ALIAS_CONTRACTS`` entry — an
  unlisted kernel, a dropped alias, or a silently added one all fail.
* **JAX004** — no f64: no equation output anywhere in the lowered
  segment (scan bodies included) carries a float64 aval.
* **JAX005** — no host callbacks inside scan bodies: a ``pure_callback``
  / ``io_callback`` / ``debug_callback`` inside the scanned round body
  would serialise every round on host round-trips.
* **JAX006** — re-dispatch fingerprint: the segment program traced for
  rounds [0, k) and for rounds [k, 2k) must produce identical jaxprs,
  so segment re-dispatch hits the jit cache instead of recompiling.

Everything here mirrors ``CompiledRunner.run``/``run_sweep`` exactly
(state init, prepare_state, device schedule slicing) so the program
checked is the program shipped.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, fedsim
from repro.core.api import _init_state, _resolve_member, _run_segment, \
    _RunState, init_fleet_global
from repro.kernels import comm_quant, ops, safa_aggregate

from .report import Report

__all__ = ['iter_cells', 'check_cells', 'lower_cell', 'precompute_cell',
           'ALIAS_CONTRACTS']

#: tiny-shape cell environment (mirrors the conformance harness sizes)
TINY_ENV = dict(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
                epochs=3, t_lim=830.0)
ROUNDS = 4          # 2 segments of...
SEG = 2             # ...2 rounds each ([0:2] checked, [2:4] fingerprinted)
ENV_SEED = 3
FLEET_SIZE = 2

ENGINES = ('scan', 'fleet')
SCHEDULES = ('dense', 'sparse', 'sparse_delta', 'sparse_tier')
WIRES = ('f32', 'int8')
KERNELS = (False, True, 'packed')

#: kernel name -> admissible input_output_aliases forms, unioned over the
#: three kernel modules' own inventories.
ALIAS_CONTRACTS = {**safa_aggregate.ALIAS_CONTRACTS, **ops.ALIAS_CONTRACTS,
                   **comm_quant.ALIAS_CONTRACTS}

_CALLBACK_PRIMS = frozenset(
    ('pure_callback', 'io_callback', 'debug_callback', 'debug_print'))

_TASK = None


def _tiny_task():
    """One shared m=5 regression task (module-cached: its jitted train
    steps and the analysis cells' traces reuse one program cache)."""
    global _TASK
    if _TASK is None:
        from repro.data import make_regression, partition
        from repro.data.tasks import regression_task
        env = _tiny_env()
        x, y = make_regression()
        data = partition(x, y, env.partition_sizes, env.m, seed=1)
        _TASK = regression_task(data, lr=1e-3, epochs=3)
    return _TASK


def _tiny_env(seed: int = ENV_SEED):
    return fedsim.EnvSpec(seed=seed, **TINY_ENV).build()


# ---------------------------------------------------------------------------
# Cell enumeration + lowering
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Cell:
    """One admitted (protocol, engine, wire, schedule, use_kernel)
    configuration; ``label`` matches the conformance-id style."""
    pdef: api.ProtocolDef
    spec: object
    ex: api.ExecSpec

    @property
    def label(self) -> str:
        ex = self.ex
        return (f'{self.pdef.name}[{ex.engine}/{ex.schedule}/{ex.wire}/'
                f'kernel={ex.use_kernel}]')


def iter_cells(names=None) -> list:
    """Every cell ``check_compat`` admits, for every registered spec (or
    the named subset)."""
    cells = []
    for pdef in api.PROTOCOLS.values():
        if names is not None and pdef.name not in names:
            continue
        spec = pdef.spec_cls()
        for engine in ENGINES:
            for schedule in SCHEDULES:
                for wire in WIRES:
                    for kern in KERNELS:
                        ex = api.ExecSpec(engine=engine, wire=wire,
                                          use_kernel=kern, schedule=schedule,
                                          eval_every=SEG)
                        try:
                            api.check_compat(spec, ex)
                        except (ValueError, TypeError):
                            continue
                        cells.append(Cell(pdef, spec, ex))
    return cells


@dataclasses.dataclass
class CellTrace:
    """Lowered artifacts of one cell: the two consecutive segment jaxprs
    and the per-segment round count."""
    cell: Cell
    jaxpr: object           # ClosedJaxpr of segment rounds [0, SEG)
    jaxpr_next: object      # ClosedJaxpr of segment rounds [SEG, 2*SEG)
    rounds: int = SEG


def _stateless(pdef, ex) -> bool:
    return (ex.schedule == 'sparse_delta' and pdef.delta_stateless) \
        or ex.schedule == 'sparse_tier'


def _member_for(spec, env, seed: int = 0) -> api.SweepMember:
    """A SweepMember replaying ``spec`` (the conformance harness
    contract): spec hypers ride the member columns, remaining fields of
    the staleness-adaptive family ride ``overrides``."""
    kw = dict(seed=seed)
    for f in ('fraction', 'lag_tolerance', 'alpha', 'staleness_exp'):
        if hasattr(spec, f):
            kw[f] = getattr(spec, f)
    if hasattr(spec, 'staleness_fn'):
        kw['overrides'] = {
            f.name: getattr(spec, f.name)
            for f in dataclasses.fields(spec)
            if f.name not in ('fraction', 'lag_tolerance', 'alpha',
                              'staleness_exp')}
    return api.SweepMember(env=env, **kw)


def precompute_cell(cell: Cell, task=None):
    """The cell's host-precomputed schedule, exactly as the runners build
    it (scan: ``Experiment.precompute``; fleet: ``fleet_precompute`` plus
    the sparse/tier form conversion) — the input of the schedule pass."""
    task = task if task is not None else _tiny_task()
    pdef, ex = cell.pdef, cell.ex
    if ex.engine == 'scan':
        exp = api.Experiment(task, _tiny_env(), cell.spec, ex,
                             rounds=ROUNDS, seed=0)
        return exp.precompute()
    members = [
        _resolve_member(_member_for(cell.spec, _tiny_env(ENV_SEED + s),
                                    seed=s),
                        pdef=pdef, task=task, ex=ex)
        for s in range(FLEET_SIZE)]
    fleet = pdef.fleet_precompute(members, cell.spec, rounds=ROUNDS)
    if ex.schedule == 'sparse_tier':
        fleet = fleet.to_tier()
    elif ex.schedule != 'dense':
        fleet = fleet.to_sparse()
    return fleet


def lower_cell(cell: Cell, task=None, env=None,
               rounds: int = ROUNDS) -> CellTrace:
    """Build the cell's segment program exactly as ``CompiledRunner``
    does and trace it (no execution, no compile).  ``task``/``env``
    default to the analysis's tiny regression federation; a single run
    (``engine='scan'``) takes any task, env and round count."""
    task = task if task is not None else _tiny_task()
    pdef, ex = cell.pdef, cell.ex
    form = pdef.form(ex)
    stateless = _stateless(pdef, ex)
    if ex.engine == 'scan':
        exp = api.Experiment(task, env if env is not None else _tiny_env(),
                             cell.spec, ex, rounds=rounds, seed=0)
        sched = exp.precompute()
        st = _init_state(task, exp.env.m, exp.seed, pdef.uses_cache,
                         stateless)
        weights = jnp.asarray(exp.env.weights)
        if pdef.prepare_state is not None:
            pdef.prepare_state(st, weights, ex, False, sched)
        train_fn = task.local_train_rows if ex.schedule != 'dense' \
            else task.local_train
        dev = sched.to_device()
        spec_static = st.spec

        # a FRESH function object per trace: make_jaxpr caches on
        # (fn identity, avals), and a cache hit would make the JAX006
        # fingerprint comparison vacuously true
        def make_seg_fn():
            def seg_fn(tree, seg, w):
                st2 = _RunState()
                st2.set_tree(tree)
                st2.spec = spec_static
                _run_segment(form, 'scan', st2, seg, w, train_fn, ex)
                return st2.tree()
            return seg_fn

        def seg_at(start):
            return jax.tree.map(lambda a: a[start:start + SEG], dev)

        j1 = jax.make_jaxpr(make_seg_fn())(st.tree(), seg_at(0), weights)
        j2 = jax.make_jaxpr(make_seg_fn())(st.tree(), seg_at(SEG), weights)
        return CellTrace(cell, j1, j2)

    # fleet engine — mirrors run_sweep's shared-task path
    members = [
        _resolve_member(_member_for(cell.spec, _tiny_env(ENV_SEED + s),
                                    seed=s),
                        pdef=pdef, task=task, ex=ex)
        for s in range(FLEET_SIZE)]
    m = members[0].env.m
    fleet = pdef.fleet_precompute(members, cell.spec, rounds=ROUNDS)
    if ex.schedule == 'sparse_tier':
        fleet = fleet.to_tier()
    elif ex.schedule != 'dense':
        fleet = fleet.to_sparse()
    weights = jnp.asarray(np.stack([mem.env.weights for mem in members]))
    g = init_fleet_global(task, [mem.seed for mem in members])

    def bcast():
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[:, None],
                                       (a.shape[0], m) + a.shape[1:]), g)

    if stateless:
        st = _RunState(g, None, None)
    else:
        st = _RunState(g, bcast(), bcast() if pdef.uses_cache else None)
    if pdef.prepare_state is not None:
        pdef.prepare_state(st, weights, ex, True, fleet)
    train_fn = task.local_train_rows if ex.schedule != 'dense' \
        else task.local_train
    dev = fleet.to_device()
    spec_static = st.spec

    def make_seg_fn():      # fresh per trace — see the scan path
        def seg_fn(tree, seg, w):
            st2 = _RunState()
            st2.set_tree(tree)
            st2.spec = spec_static
            _run_segment(form, 'fleet', st2, seg, w, train_fn, ex)
            return st2.tree()
        return seg_fn

    def seg_at(start):
        return jax.tree.map(lambda a: a[:, start:start + SEG], dev)

    j1 = jax.make_jaxpr(make_seg_fn())(st.tree(), seg_at(0), weights)
    j2 = jax.make_jaxpr(make_seg_fn())(st.tree(), seg_at(SEG), weights)
    return CellTrace(cell, j1, j2)


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        for v in (p if isinstance(p, (tuple, list)) else (p,)):
            if hasattr(v, 'eqns'):                           # Jaxpr
                yield v
            elif hasattr(getattr(v, 'jaxpr', None), 'eqns'):  # ClosedJaxpr
                yield v.jaxpr

def _walk_eqns(jaxpr, *, in_scan=False):
    """Yield (eqn, in_scan) over every equation, descending into nested
    jaxprs; ``in_scan`` marks equations inside any ``scan`` body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        inner = in_scan or eqn.primitive.name == 'scan'
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, in_scan=inner)


def _kernel_name(eqn) -> str:
    """Kernel body name of a pallas_call eqn, from the kernel jaxpr's
    debug info (``'<body> at <file>:<line>'``); vmapped launches keep
    the body's name, so names key into the modules' ALIAS_CONTRACTS."""
    return eqn.params['jaxpr'].debug_info.func_src_info.split(' at ')[0]


def _pallas_sites(jaxpr):
    """[(kernel_name, alias_pairs, in_scan)] for every pallas_call eqn.
    ``in_scan`` distinguishes per-round dispatches (inside the scanned
    round body — issued once per round) from per-segment ones."""
    out = []
    for eqn, in_scan in _walk_eqns(jaxpr):
        if eqn.primitive.name == 'pallas_call':
            pairs = tuple(tuple(p) for p in
                          eqn.params.get('input_output_aliases', ()))
            out.append((_kernel_name(eqn), pairs, in_scan))
    return out


def _check_donations(jaxpr):
    """JAX002: for every jit eqn with donated invars, each donated
    buffer must be matchable 1:1 to an output aval (shape+dtype) —
    the necessary condition for XLA to honour the donation.  Returns
    (ok, detail)."""
    for eqn, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name != 'jit':
            continue
        donated = eqn.params.get('donated_invars', ())
        if not any(donated):
            continue
        outs = [(v.aval.shape, v.aval.dtype) for v in eqn.outvars]
        name = eqn.params.get('name', '<jit>')
        for i, (inv, don) in enumerate(zip(eqn.invars, donated)):
            if not don:
                continue
            key = (inv.aval.shape, inv.aval.dtype)
            if key in outs:
                outs.remove(key)    # each output absorbs one donation
            else:
                return False, (
                    f'jit {name!r}: donated input {i} '
                    f'{inv.aval.str_short()} has no matching output '
                    f'buffer — XLA drops the donation (hidden copy)')
    return True, 'all donated buffers have matching outputs'


def _check_dtypes_and_callbacks(jaxpr):
    """JAX004 + JAX005 in one walk."""
    f64_detail = callback_detail = None
    for eqn, in_scan in _walk_eqns(jaxpr):
        if f64_detail is None:
            for v in eqn.outvars:
                dt = getattr(v.aval, 'dtype', None)
                if dt is not None and dt == jnp.float64:
                    f64_detail = (f'{eqn.primitive.name} produces f64 '
                                  f'{v.aval.str_short()}')
                    break
        if callback_detail is None and in_scan \
                and eqn.primitive.name in _CALLBACK_PRIMS:
            callback_detail = (f'{eqn.primitive.name} inside a scanned '
                               f'round body (host sync every round)')
    return f64_detail, callback_detail


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------

def check_cells(names=None, task=None, cells=None) -> Report:
    """Run JAX001-006 over every admitted cell of the registry (or the
    named protocols)."""
    rep = Report()
    for cell in (cells if cells is not None else iter_cells(names)):
        label = cell.label
        try:
            tr = lower_cell(cell, task=task)
        except Exception as e:      # lowering itself must not break
            rep.add('JAX001', label, False,
                    f'cell failed to lower: {type(e).__name__}: {e}')
            continue
        sites = _pallas_sites(tr.jaxpr.jaxpr)
        per_round = sum(1 for _, _, in_scan in sites if in_scan)
        per_seg = len(sites) - per_round

        # JAX001 — dispatch budget per round (static count in the
        # scanned round body; dispatches outside the scan are
        # per-segment overhead, reported but not budgeted)
        budget = (cell.pdef.dispatch_budget(cell.ex)
                  if cell.pdef.dispatch_budget is not None else None)
        if budget is None:
            rep.add('JAX001', label, True,
                    f'no budget declared (measured {per_round}/round '
                    f'+ {per_seg}/segment)')
        else:
            rep.add('JAX001', label, per_round == budget,
                    f'{per_round} dispatches/round vs budget {budget} '
                    f'(+ {per_seg}/segment)')

        # JAX002 — donations take effect
        ok, detail = _check_donations(tr.jaxpr.jaxpr)
        rep.add('JAX002', label, ok, detail)

        # JAX003 — alias claims present + inventory consistency
        claims = (cell.pdef.alias_claims(cell.ex)
                  if cell.pdef.alias_claims is not None else {})
        found = {(kname, pairs) for kname, pairs, _ in sites}
        missing = {
            kname: pairs for kname, pairs in (claims or {}).items()
            if (kname, tuple(pairs)) not in found}
        bad = [
            f'{kname} lowered with aliases {pairs} not admitted by its '
            f'module ALIAS_CONTRACTS entry '
            f'{ALIAS_CONTRACTS.get(kname, "<unlisted kernel>")}'
            for kname, pairs in sorted(found)
            if pairs not in ALIAS_CONTRACTS.get(kname, ())]
        if missing:
            rep.add('JAX003', label, False,
                    f'claimed aliases missing from lowered module: '
                    f'{missing} (found sites: {sorted(found)})')
        elif bad:
            rep.add('JAX003', label, False, bad[0])
        else:
            rep.add('JAX003', label, True,
                    f'{len(claims or {})} claim(s) present, '
                    f'{len(found)} pallas site(s) all in inventory')

        # JAX004 / JAX005 — f64 promotion, host callbacks in scan bodies
        f64, cb = _check_dtypes_and_callbacks(tr.jaxpr.jaxpr)
        rep.add('JAX004', label, f64 is None, f64 or 'no f64 avals')
        rep.add('JAX005', label, cb is None,
                cb or 'no host callbacks in scan bodies')

        # JAX006 — segment re-dispatch fingerprint
        same = str(tr.jaxpr) == str(tr.jaxpr_next)
        rep.add('JAX006', label, same,
                'consecutive segments trace to identical jaxprs'
                if same else 'segment [k, 2k) traces to a different '
                'jaxpr than [0, k): re-dispatch recompiles')
    return rep


def survey(names=None) -> None:
    """Print measured dispatch counts and pallas sites per cell (the data
    the registry budgets were pinned from)."""
    for cell in iter_cells(names):
        try:
            tr = lower_cell(cell)
        except Exception as e:
            print(f'{cell.label}: LOWERING FAILED {type(e).__name__}: {e}')
            continue
        sites = _pallas_sites(tr.jaxpr.jaxpr)
        per_round = [(k, p) for k, p, s in sites if s]
        per_seg = [(k, p) for k, p, s in sites if not s]
        print(f'{cell.label}: {len(per_round)}/round {sorted(per_round)}; '
              f'{len(per_seg)}/segment {sorted(per_seg)}')


if __name__ == '__main__':      # pragma: no cover - dev helper
    import sys
    survey(set(sys.argv[1:]) or None)
