"""Unified experiment API: declarative specs -> protocol registry ->
compiled, resumable runners.

One spec, one compile step, many protocols::

    from repro import api

    exp = api.Experiment(task, env,
                         api.SafaSpec(fraction=0.5, lag_tolerance=5),
                         api.ExecSpec(eval_every=15),
                         rounds=60)
    hist = exp.compile().run()

The pieces:

* **Protocol specs** (``SafaSpec``/``FedAvgSpec``/``FedCSSpec``/
  ``LocalSpec``/``FedAsyncSpec``) are frozen dataclasses carrying only
  protocol-semantic fields; **``ExecSpec``** carries execution knobs
  (``engine``, ``wire``, ``use_kernel``, ``shard``, ``eval_every``,
  ``numeric``).  All cross-field validation lives in ``check_compat``.
* **``PROTOCOLS``** maps each spec type to a ``ProtocolDef`` — the
  protocol's precompute / scan / fleet triple plus its loop-engine round
  — so a new variant (say, a SEAFL-style staleness-discounted
  aggregation) registers with ``api.register`` and immediately gains
  every engine, sweep batching, and checkpointing, without touching
  ``federation.py``.
* **``Experiment``** binds (task, env, protocol spec, exec spec, rounds,
  seed); ``.precompute()`` runs the host event state machine once (the
  env rng is consumed exactly once, the schedule is cached) and
  ``.compile()`` returns a ``CompiledRunner``.
* **``CompiledRunner.run()``** executes the single run;
  ``.run_sweep(members)`` executes S member configurations as a batched
  fleet (``SweepSpec(members, tasks=...)`` for per-member Tasks via
  padded stacking).  Both accept ``checkpoint=`` for kill/resume: the
  scan carry and the host schedule cursor persist at every eval-segment
  boundary (``repro.checkpoint``), and a resumed run finishes
  bit-identical to an uninterrupted one.

The legacy free functions (``federation.run_safa`` & co.) are thin shims
over this module and emit ``DeprecationWarning``.
"""
from __future__ import annotations

import copy
import dataclasses
import hashlib
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import fedsim, obs
from repro.core import federation, protocol, schedules
from repro.core.federation import Task
from repro.core.schedules import History, RoundRecord, SweepMember
from repro.kernels.backend import row_pad

__all__ = [
    'CompiledRunner', 'ExecSpec', 'Experiment', 'FedAsyncSpec', 'FedAvgSpec',
    'FedCSSpec', 'Form', 'History', 'LocalSpec', 'PROTOCOLS', 'ProtocolDef',
    'ProtocolSpec', 'RoundRecord', 'STALENESS_FNS', 'SafaSpec', 'SweepMember',
    'SweepSpec', 'Task', 'check_compat', 'init_fleet_global', 'register',
    'spec',
]


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProtocolSpec:
    """Base class for protocol specs: protocol-semantic fields only —
    execution knobs live in ``ExecSpec``."""


@dataclasses.dataclass(frozen=True)
class SafaSpec(ProtocolSpec):
    """SAFA (the paper's protocol): post-training CFCFM selection at
    quota C*m, Eq. 3 lag-tolerant distribution, Eq. 6-8 three-bypass
    aggregation.  ``quantize_uploads`` is the per-leaf int8 reference
    form of the packed ``wire='int8'`` path (mutually exclusive)."""
    fraction: float = 0.5
    lag_tolerance: int = 5
    quantize_uploads: bool = False


@dataclasses.dataclass(frozen=True)
class FedAvgSpec(ProtocolSpec):
    """FedAvg baseline: random pre-training selection, synchronous.

    ``sampler`` picks the without-replacement draw: ``'choice'`` (default)
    is the legacy per-round ``Generator.choice`` stream; ``'topk'`` is the
    vectorised bulk-uniform draw (one ``rng.random((rounds, m))``) that
    scales to large populations — distributionally identical, different
    stream by design."""
    fraction: float = 0.5
    sampler: str = 'choice'


@dataclasses.dataclass(frozen=True)
class FedCSSpec(ProtocolSpec):
    """FedCS baseline: fastest-first selection under the deadline."""
    fraction: float = 0.5


@dataclasses.dataclass(frozen=True)
class LocalSpec(ProtocolSpec):
    """Fully-local baseline: no aggregation except at eval points."""
    fraction: float = 0.5


#: staleness-discount functions s(dt) of the FedAsync family (Xie et al.):
#: ``'constant'`` -> 1; ``'hinge'`` -> 1 if dt <= b else 1/(a*(dt-b)),
#: clamped to (0, 1]; ``'poly'`` -> (1+dt)^(-a).  The discount scales the
#: base mixing weight alpha, so every variant replays through the same
#: precomputed per-round alpha tensors.
STALENESS_FNS = ('constant', 'hinge', 'poly')


@dataclasses.dataclass(frozen=True)
class FedAsyncSpec(ProtocolSpec):
    """FedAsync baseline: every client, every round; merge-per-arrival
    with staleness-discounted mixing alpha * s(staleness).

    ``staleness_fn`` picks s(dt) from ``STALENESS_FNS``; the default
    ``'poly'`` is the legacy alpha*(1+staleness)^(-staleness_exp) form,
    bit-identical to the pre-``staleness_fn`` schedules.  ``hinge_a`` /
    ``hinge_b`` parameterise the hinge discount (ignored otherwise)."""
    alpha: float = 0.6
    staleness_exp: float = 0.5
    staleness_fn: str = 'poly'
    hinge_a: float = 10.0
    hinge_b: int = 4


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Execution knobs, orthogonal to protocol semantics.

    ``engine=None`` resolves to the compiled default: ``'scan'`` for
    ``run()``, ``'fleet'`` for ``run_sweep()``; the reference engines
    (``'loop'`` / ``'sequential'``) stay available and bit-identical.

    ``schedule`` picks the schedule representation and round math:

    * ``'dense'`` — [rounds, m] masks, every client's row flows through
      every round (the paper-scale reference).
    * ``'sparse'`` — [rounds, quota] (idx, roles) tensors; only the
      active rows are trained, then the identical dense server trace
      runs.  Bit-identical to ``'dense'``, training FLOPs O(quota).
    * ``'sparse_delta'`` — additionally keeps the aggregation O(quota·N)
      per round by carrying the running weighted sum as a delta target.
      Allclose- (not bit-) equivalent; with ``use_kernel='packed'``
      (SAFA) the whole round fuses into one rows-indexed dispatch on
      resident pack buffers.
    * ``'sparse_tier'`` — (SAFA) replaces the remaining [m, N] cache
      stack with a lag-tier value buffer of capacity + 1 rows
      (capacity = peak live version snapshots + commit rows,
      O(tau+quota)) plus host-precomputed slot maps.  Resident state is
      O((tau+quota)·N) — independent of m.  Same slot math as
      ``'sparse_delta'`` (allclose to it and to ``'dense'``); scan==loop
      and fleet==sequential stay bit-identical within the form."""
    engine: Optional[str] = None
    wire: str = 'f32'
    use_kernel: Any = False
    schedule: str = 'dense'
    shard: bool = True
    eval_every: int = 10
    numeric: bool = True


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A sweep: S member configurations, optionally with per-member
    ``tasks`` (one per member, padded-stacked so members may hold
    different client partitions — multi-``seed`` env sweeps batch too)."""
    members: tuple
    tasks: Optional[tuple] = None

    def __post_init__(self):
        object.__setattr__(self, 'members', tuple(self.members))
        if self.tasks is not None:
            object.__setattr__(self, 'tasks', tuple(self.tasks))
            if len(self.tasks) != len(self.members):
                raise ValueError(
                    f'got {len(self.tasks)} tasks for {len(self.members)} '
                    f'members (want one task per member, or tasks=None '
                    f'for a shared task)')


# ---------------------------------------------------------------------------
# Protocol registry
# ---------------------------------------------------------------------------

class Form(NamedTuple):
    """One state form of a protocol, as the runners drive it: the name of
    its scan engine on ``protocol`` (whose step adapter and fleet twin
    ``protocol.ENGINES[engine]`` names), the ``_RunState`` fields its
    carry is made of, in order, and whether the carry is instead the
    state's pack buffers (``_RunState.packed``), whose global the runner
    unpacks after every segment."""
    engine: str
    carry: tuple = ()
    packed: bool = False


@dataclasses.dataclass(frozen=True)
class ProtocolDef:
    """Everything the runners need to execute one protocol.

    ``precompute(env, spec, *, rounds, seed)`` runs the host event state
    machine; ``fleet_precompute(members, *, rounds)`` the fleet-major
    form.  ``form(ex)`` names the ``Form`` an exec spec runs: the runners
    drive every form through one generic segment path (``_run_segment``).
    ``finish_segment`` (optional) runs at eval stops (the fully-local
    aggregation).  Registering a new def via ``register`` makes the
    protocol available to ``Experiment`` and sweeps without touching
    ``federation.py``."""
    name: str
    spec_cls: type
    precompute: Callable
    fleet_precompute: Callable
    form: Callable
    finish_segment: Optional[Callable] = None
    uses_cache: bool = False
    supports_wire: bool = False
    #: fused-aggregation kernel support: ``False`` (no kernel), ``True``
    #: (both the per-leaf kernel and the packed one), or ``'packed'`` —
    #: the protocol's merge only exists on pack buffers, so ``use_kernel``
    #: takes ``False`` or ``'packed'`` but never ``True`` (the weighted
    #: aggregation family has no leaf-wise kernel form).
    supports_kernel: Any = False
    #: sparse-schedule support (``ExecSpec.schedule != 'dense'``):
    #: ``sparse_precompute(env, spec, *, rounds, seed)`` emits the native
    #: [rounds, quota] schedule (None -> protocol rejects sparse);
    #: ``prepare_state(st, weights, ex, fleet)`` converts the initial
    #: model state for the schedule mode (running aggregate, pack
    #: buffers, dropping stateless carries) before any round runs.
    sparse_precompute: Optional[Callable] = None
    prepare_state: Optional[Callable] = None
    #: lag-tier schedule support (``ExecSpec.schedule == 'sparse_tier'``):
    #: ``tier_precompute(env, spec, *, rounds, seed)`` emits the
    #: [rounds, quota] (idx, roles) tensors plus the slot maps over the
    #: O(tau+quota) value buffer (None -> protocol rejects sparse_tier).
    tier_precompute: Optional[Callable] = None
    #: the protocol's sparse_delta carry is the global model alone (no
    #: [m, ...] local/cache stacks): the runners then never materialise
    #: the O(m) state — resident memory stays quota-bounded at any m.
    delta_stateless: bool = False
    #: the protocol's precompute consumes leftover ``SweepMember.overrides``
    #: keys as protocol-spec fields (the staleness-adaptive family).  When
    #: False, override keys that are not ``EnvSpec`` fields are rejected
    #: at sweep-resolution time with a golden message.
    spec_overrides: bool = False
    #: static dispatch budget for ``repro.analysis`` (JAX001):
    #: ``dispatch_budget(ex)`` returns the pallas dispatches one compiled
    #: round of the admitted exec cell issues, or None for cells with no
    #: declared budget (e.g. the leaf-wise kernel path, whose count
    #: scales with the model's pytree).  This is where "a fully
    #: compressed SAFA round is exactly 2 dispatches" lives as data.
    dispatch_budget: Optional[Callable] = None
    #: static alias claims for ``repro.analysis`` (JAX003):
    #: ``alias_claims(ex)`` returns {kernel body name: alias pairs} that
    #: must appear, exactly, among the cell's lowered pallas_call sites;
    #: names/pairs key into the kernel modules' ``ALIAS_CONTRACTS``.
    alias_claims: Optional[Callable] = None


#: spec type -> ProtocolDef.  The single source of protocol dispatch.
PROTOCOLS: dict = {}
_BY_NAME: dict = {}


def register(pdef: ProtocolDef) -> ProtocolDef:
    """Add a protocol to the registry (spec type and name must be new)."""
    if pdef.spec_cls in PROTOCOLS:
        raise ValueError(f'spec type {pdef.spec_cls.__name__} already '
                         f'registered (as {PROTOCOLS[pdef.spec_cls].name!r})')
    if pdef.name in _BY_NAME:
        raise ValueError(f'protocol name {pdef.name!r} already registered')
    PROTOCOLS[pdef.spec_cls] = pdef
    _BY_NAME[pdef.name] = pdef
    return pdef


def spec(name: str, **fields) -> ProtocolSpec:
    """Build a protocol spec by registry name ('safa', 'fedavg', ...)."""
    if name not in _BY_NAME:
        raise ValueError(
            f'unknown proto {name!r} (want one of {sorted(_BY_NAME)})')
    return _BY_NAME[name].spec_cls(**fields)


def check_compat(protocol_spec: ProtocolSpec,
                 exec_spec: Optional[ExecSpec] = None,
                 env=None) -> ProtocolDef:
    """Validate a (protocol, exec[, env]) spec triple; returns the
    ProtocolDef.

    This is the single home for every cross-field rule the legacy
    runners enforced ad hoc: wire values, engine names, kernel modes,
    wire x protocol compatibility, and the quantize_uploads-vs-wire
    exclusivity.  ``env`` (optional) is an ``fedsim.EnvSpec`` — or a
    built ``Env``, validated through its spec — checked with the same
    golden messages ``EnvSpec.build()`` raises."""
    pdef = PROTOCOLS.get(type(protocol_spec))
    if pdef is None:
        raise TypeError(
            f'unregistered protocol spec {type(protocol_spec).__name__!r}; '
            f'known specs: {sorted(c.__name__ for c in PROTOCOLS)} '
            f'(register new ones via api.register)')
    ex = exec_spec if exec_spec is not None else ExecSpec()
    if env is not None:
        env_spec = getattr(env, 'spec', env)
        if isinstance(env_spec, fedsim.EnvSpec):
            fedsim.validate_env_spec(env_spec)
    protocol.check_wire(ex.wire)
    if ex.engine not in (None, 'scan', 'loop', 'fleet', 'sequential'):
        raise ValueError(
            f'unknown engine {ex.engine!r} (want "scan"/"loop" for runs, '
            f'"fleet"/"sequential" for sweeps, or None for the default)')
    if ex.use_kernel not in (False, True, 'packed'):
        raise ValueError(
            f'unknown use_kernel {ex.use_kernel!r} (want False, True, or '
            f'"packed")')
    if ex.wire != 'f32' and not pdef.supports_wire:
        wired = '/'.join(sorted(p.name for p in PROTOCOLS.values()
                                if p.supports_wire))
        raise ValueError(
            f"protocol {pdef.name!r} has no upload-aggregate wire; "
            f"wire='int8' applies to {wired} only")
    if ex.use_kernel and not pdef.supports_kernel:
        kerneled = '/'.join(sorted(p.name for p in PROTOCOLS.values()
                                   if p.supports_kernel))
        raise ValueError(
            f'protocol {pdef.name!r} has no fused aggregation kernel; '
            f'use_kernel applies to {kerneled} only')
    if ex.use_kernel is True and pdef.supports_kernel == 'packed':
        raise ValueError(
            f'protocol {pdef.name!r} aggregates on pack buffers only (no '
            f"leaf-wise kernel form); use_kernel takes False or 'packed'")
    fn = getattr(protocol_spec, 'staleness_fn', None)
    if fn is not None and fn not in STALENESS_FNS:
        raise ValueError(
            f'unknown staleness_fn {fn!r} (want one of {STALENESS_FNS})')
    alpha = getattr(protocol_spec, 'alpha', None)
    if alpha is not None and not 0.0 < alpha <= 1.0:
        raise ValueError(
            f'alpha must be in (0, 1] (the residual global weight '
            f'1 - sum(wrow) must stay non-negative), got {alpha}')
    if getattr(protocol_spec, 'hinge_a', 1.0) <= 0:
        raise ValueError(
            f'hinge_a must be > 0, got {protocol_spec.hinge_a}')
    if getattr(protocol_spec, 'clusters', 1) < 1:
        raise ValueError(
            f'clusters must be >= 1, got {protocol_spec.clusters}')
    if getattr(protocol_spec, 'quantize_uploads', False) and ex.wire != 'f32':
        raise ValueError(
            "quantize_uploads=True is the per-leaf reference for the packed "
            "wire='int8' path; pass one or the other, not both")
    if getattr(protocol_spec, 'sampler', 'choice') not in ('choice', 'topk'):
        raise ValueError(
            f'unknown sampler {protocol_spec.sampler!r} '
            f"(want 'choice' or 'topk')")
    if ex.schedule not in ('dense', 'sparse', 'sparse_delta', 'sparse_tier'):
        raise ValueError(
            f'unknown schedule {ex.schedule!r} (want "dense", "sparse", '
            f'"sparse_delta", or "sparse_tier")')
    if ex.schedule != 'dense':
        if pdef.sparse_precompute is None:
            raise ValueError(
                f'protocol {pdef.name!r} has no sparse schedule form; '
                f'sparse schedules apply to safa/fedavg/fedcs only')
        if ex.schedule == 'sparse_tier' and pdef.tier_precompute is None:
            raise ValueError(
                f'protocol {pdef.name!r} has no lag-tier schedule form; '
                f"schedule='sparse_tier' applies to safa only (the "
                f'version-ring compression needs SAFA lag-bounded bases)')
        if getattr(protocol_spec, 'quantize_uploads', False):
            raise ValueError(
                'quantize_uploads is the dense per-leaf reference knob; '
                "sparse schedules take the packed wire instead "
                "(wire='int8')")
        if ex.schedule in ('sparse_delta', 'sparse_tier') \
                and ex.use_kernel is True:
            raise ValueError(
                f'the leaf-wise kernel (use_kernel=True) has no rows form; '
                f"schedule={ex.schedule!r} takes use_kernel=False or "
                f"'packed'")
    return pdef


# ---------------------------------------------------------------------------
# Engine plumbing (shared by every protocol def)
# ---------------------------------------------------------------------------

class _RunState:
    """The model-state carry between segments: global/local(/cache).

    Sparse-delta modes add ``agg`` (the running Eq. 7 aggregate) and,
    under ``use_kernel='packed'``, ``packed`` — the (global, local,
    cache, agg) pack-buffer carry with layout ``spec`` (static, rebuilt
    on resume) that replaces the local/cache/agg trees entirely."""
    __slots__ = ('global_w', 'local_w', 'cache', 'agg', 'packed', 'spec')

    def __init__(self, global_w=None, local_w=None, cache=None):
        self.global_w, self.local_w, self.cache = global_w, local_w, cache
        self.agg, self.packed, self.spec = None, None, None

    def tree(self):
        t = {'global': self.global_w, 'local': self.local_w}
        if self.cache is not None:
            t['cache'] = self.cache
        if self.agg is not None:
            t['agg'] = self.agg
        if self.packed is not None:
            t['packed'] = self.packed
        return t

    def set_tree(self, t):
        self.global_w, self.local_w = t['global'], t['local']
        self.cache = t.get('cache')
        self.agg = t.get('agg')
        self.packed = t.get('packed')


def _eval_rounds(rounds: int, eval_every: int):
    """Rounds at which the runners evaluate the global model.

    These are also the scan-engine segment boundaries — and therefore the
    checkpoint/resume boundaries: at most two distinct segment lengths
    exist per run (eval_every and a ragged final remainder), so the
    scanned program traces at most twice."""
    stops = sorted(set(range(eval_every, rounds + 1, eval_every)) | {rounds})
    return [t for t in stops if t >= 1]


def _record_eval(hist: History, rec: RoundRecord, task, global_w):
    with obs.span('evaluate'):
        rec.eval = task.evaluate(global_w)
    if hist.best_eval is None or rec.eval['loss'] < hist.best_eval['loss']:
        hist.best_eval = rec.eval


def _stack_trees(trees):
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def _tree_member(tree, s: int):
    return jax.tree.map(lambda a: a[s], tree)


def _init_state(task, m: int, seed: int, uses_cache: bool,
                stateless: bool = False) -> _RunState:
    key = jax.random.PRNGKey(seed)
    g = task.init_global(key)
    if stateless:           # sparse_delta with a global-only carry: never
        return _RunState(g, None, None)   # materialise the [m, ...] stacks
    return _RunState(g, protocol.broadcast_global(g, m),
                     protocol.broadcast_global(g, m) if uses_cache else None)


def _apply_saved_history(hist: History, d: dict) -> None:
    """Replay a checkpoint's eval entries into a freshly-precomputed
    History (records/futility are recomputed bit-identically; only the
    evals and best_eval need restoring)."""
    hist.best_eval = d['best_eval']
    for rec, rd in zip(hist.records, d['records']):
        if rd.get('eval') is not None:
            rec.eval = rd['eval']


def _fp_val(v):
    """Checkpoint-fingerprint form of one spec field value: recurse into
    nested dataclasses (trace specs), hash ndarrays (``Replay`` traces)
    so a fingerprint never embeds megabytes of trace data."""
    if isinstance(v, np.ndarray):
        digest = hashlib.sha1(np.ascontiguousarray(v).tobytes()).hexdigest()
        return f'ndarray{v.shape}:{digest}'
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return (type(v).__name__,
                [(f.name, _fp_val(getattr(v, f.name)))
                 for f in dataclasses.fields(v)])
    return v


def _env_fp(env) -> str:
    """Environment identity for checkpoint fingerprints: the declarative
    spec's fields (a built ``Env`` fingerprints as its spec — same
    spelling, same fingerprint)."""
    spec = getattr(env, 'spec', env)
    return repr(_fp_val(spec))


#: declarative env fields a ``SweepMember.overrides`` dict may set
_ENV_FIELDS = frozenset(f.name for f in dataclasses.fields(fedsim.EnvSpec))


def _wire_mb_of(task, wire: str):
    """Measured (uplink, downlink) megabytes of the task's model under the
    active wire (``EnvSpec(comm='wire')``): the uplink ships client
    updates — packed int8 buffers under ``wire='int8'``, plain f32 leaves
    otherwise — while the server always distributes the uncompressed
    global.  Memoised on the task (one throwaway ``init_global`` per
    distinct wire) so sweeps measure once."""
    from repro.kernels import ops as kops
    cache = task.__dict__.setdefault('_wire_mb_cache', {})
    if wire not in cache:
        g = task.init_global(jax.random.PRNGKey(0))
        up = kops.comm_bytes(g, wire == 'int8',
                             layout='packed' if wire == 'int8' else 'tree')
        down = kops.comm_bytes(g, False, layout='tree')
        cache[wire] = (up / 1e6, down / 1e6)
    return cache[wire]


def _realize_env(env, *, task, ex):
    """``EnvSpec`` -> built ``Env``; built envs pass through.  When the
    spec asks for wire-derived comm (``comm='wire'``), measure the task
    model's actual bytes under ``ex.wire`` and inject them before any
    schedule precompute runs."""
    if env is None:
        return None
    if isinstance(env, fedsim.EnvSpec):
        env = env.build()
    if getattr(env, 'comm', 'static') == 'wire':
        if task is None:
            raise ValueError(
                "EnvSpec(comm='wire') derives comm times from the "
                'experiment model; this run has no Task to measure '
                "(pass a Task, or use comm='static')")
        env.set_wire_mb(*_wire_mb_of(task, ex.wire))
    return env


def _resolve_member(mem: SweepMember, *, pdef: ProtocolDef, task,
                    ex: ExecSpec) -> SweepMember:
    """Split a member's overrides into env fields vs protocol fields,
    apply the env part declaratively, and realize the env.

    Env-field overrides (``crash_prob``, ``traces``, ...) need a
    declarative member env — an ``fedsim.EnvSpec`` — so the override is a
    pure ``dataclasses.replace`` before the population is drawn; leftover
    keys must be protocol-spec fields of a ``spec_overrides`` protocol
    (the staleness-adaptive family), rejected here otherwise."""
    env = mem.env
    ov = dict(mem.overrides or {})
    env_ov = {k: ov.pop(k) for k in list(ov) if k in _ENV_FIELDS}
    if env_ov:
        if not isinstance(env, fedsim.EnvSpec):
            raise ValueError(
                f'member override keys {sorted(env_ov)} are EnvSpec fields; '
                f'env overrides need a declarative member env '
                f'(fedsim.EnvSpec), got {type(env).__name__}')
        env = env.replace(**env_ov)
    if ov and not pdef.spec_overrides:
        raise ValueError(
            f'unknown member override keys {sorted(ov)}; protocol '
            f'{pdef.name!r} takes env-field overrides only '
            f'(EnvSpec fields, e.g. crash_prob/traces/draw_seed)')
    return dataclasses.replace(mem, env=_realize_env(env, task=task, ex=ex),
                               overrides=(ov or None))


def _operands_of(task):
    """``task.operands()`` (``Task.operands``), looked up through a
    wrapper that delegates to the task it holds as ``.task``."""
    while task is not None:
        fn = getattr(task, 'operands', None)
        if fn is not None:
            return fn()
        task = getattr(task, 'task', None)
    return None


class _WithOperands:
    """A task whose training takes its operands (``Task.operands``) as a
    third argument, with them bound: ``local_train(stacked, round_idx)``."""

    def __init__(self, task, operands):
        self._task, self._operands = task, operands

    def local_train(self, stacked, round_idx):
        return self._task.local_train(stacked, round_idx, self._operands)


def _operand_train_fn(task):
    """``task``'s training as the engine calls it when the task has
    operands: ``train(stacked, round_idx, operands)``.  A task that owns
    them takes them itself.  A wrapper that holds one as ``.task`` and
    forwards only ``(stacked, round_idx)`` to it (a harness's adapter)
    runs as it is, around the task with the operands bound: a shallow
    copy of it, made while the segment is traced, holds that instead."""
    if getattr(task, 'operands', None) is not None:
        return task.local_train

    def bind(t, operands):
        if getattr(t, 'operands', None) is not None:
            return _WithOperands(t, operands)
        outer = copy.copy(t)
        outer.task = bind(t.task, operands)
        return outer

    def train(stacked, round_idx, operands):
        return bind(task, operands).local_train(stacked, round_idx)
    return train


def _task_fp(task) -> str:
    """Task identity for checkpoint fingerprints.  Tasks that implement
    ``fingerprint()`` (e.g. ``SupervisedTask``: a hash of the client
    data + hypers) pin the training problem; others fall back to the
    class name, which at least catches swapping task types."""
    if task is None:
        return 'None'
    fp = getattr(task, 'fingerprint', None)
    return fp() if callable(fp) else type(task).__name__


def _fresh_records(records: list) -> list:
    """Per-run copies of a schedule's RoundRecords.  The schedule is
    cached on the Experiment, so Histories from repeated run() calls
    must not alias (and thereby leak evals into) each other's records."""
    return [dataclasses.replace(r, eval=None) for r in records]


def init_fleet_global(task, seeds):
    """Per-member initial globals for a shared-task fleet, stacked [S, ...].

    This codifies the fleet-init contract: ``task.init_global`` is called
    host-side once per *distinct* seed and the results are stacked — it is
    deliberately NOT vmapped over a key batch, because vmapping a
    PRNG-keyed init lowers ``jax.random`` differently than the scalar call
    and is not bit-stable against the single-run path.  Members sharing a
    seed therefore share one init computation, and every member's row is
    bit-identical to its own ``task.init_global(PRNGKey(seed))`` — which is
    what keeps ``engine='fleet'`` == ``engine='sequential'`` == single
    ``run()`` exact.  The relaxed part of the contract is only *where* the
    init runs (host loop, outside the compiled fleet program), never its
    values."""
    init = {}
    for seed in seeds:
        if seed not in init:
            init[seed] = task.init_global(jax.random.PRNGKey(seed))
    return _stack_trees([init[seed] for seed in seeds])


def _stacked_task(tasks):
    """Memoised ``stack_tasks``: repeated ``run_sweep`` calls over the
    same task tuple (e.g. the checkpoint resume flow) reuse one stacked
    task, so the padded data is built once and the bound ``fleet_train``
    stays a stable static jit argument (a fresh one would force a full
    recompile).  Cached on the first task; entries hold the member tasks
    alive, so the id-tuple key cannot be reused while it is live."""
    from repro.data.tasks import stack_tasks
    cache = tasks[0].__dict__.setdefault('_fleet_task_stacks', {})
    key = tuple(map(id, tasks))
    if key not in cache:
        cache[key] = stack_tasks(tasks)
    return cache[key]


# ---------------------------------------------------------------------------
# Built-in protocol defs
# ---------------------------------------------------------------------------

def _safa_precompute(env, sp, *, rounds, seed):
    del seed  # SAFA's event process draws only from the env rng
    return federation.precompute_safa_schedule(
        env, fraction=sp.fraction, lag_tolerance=sp.lag_tolerance,
        rounds=rounds)


def _safa_sparse_precompute(env, sp, *, rounds, seed):
    del seed
    return federation.precompute_safa_schedule(
        env, fraction=sp.fraction, lag_tolerance=sp.lag_tolerance,
        rounds=rounds, form='sparse')


def _safa_tier_precompute(env, sp, *, rounds, seed):
    del seed
    return federation.precompute_safa_schedule(
        env, fraction=sp.fraction, lag_tolerance=sp.lag_tolerance,
        rounds=rounds, form='sparse_tier')


def _pack_layout(global_w, wire):
    from repro.kernels import ops as kops
    return kops.wire_spec(global_w) if wire == 'int8' \
        else kops.pack_spec(global_w)


_GLC = ('global_w', 'local_w', 'cache')

#: SAFA's state forms.  Under ``use_kernel='packed'`` the sparse_delta
#: and sparse_tier carries are pack buffers (``_safa_prepare_state``).
_SAFA_FORMS = {
    'dense': Form('safa_run_scan', _GLC),
    'sparse': Form('safa_run_scan_sparse', _GLC),
    'sparse_delta': Form('safa_run_scan_sparse_delta', _GLC + ('agg',)),
    'sparse_delta_packed': Form('safa_run_scan_sparse_delta_packed',
                                packed=True),
    'sparse_tier': Form('safa_run_scan_sparse_tier',
                        ('global_w', 'cache', 'agg')),
    'sparse_tier_packed': Form('safa_run_scan_sparse_tier_packed',
                               packed=True),
}


def _safa_form(ex) -> Form:
    packed = ex.use_kernel == 'packed' and ex.schedule in ('sparse_delta',
                                                          'sparse_tier')
    return _SAFA_FORMS[ex.schedule + '_packed' * packed]


def _safa_prepare_state(st, weights, ex, fleet: bool, sched=None):
    """Sparse-delta carries: the running aggregate tree, or — under
    ``use_kernel='packed'`` — the whole state as resident pack buffers
    ([m+1, N] with a trailing scratch row for sentinel slots, padded to
    whole 8-row groups for the in-place row kernels).

    Lag-tier carries (``schedule='sparse_tier'``): the [m, ...] stacks are
    never materialised — the cache slot becomes the O(tau+quota) value
    buffer of ``sched.capacity + 1`` rows (every row starts as the init
    global, matching the dense cache init bit-for-bit), and the running
    aggregate starts at ``global * sum(weights)``."""
    if ex.schedule == 'sparse_tier':
        _safa_prepare_tier_state(st, weights, ex, fleet, sched)
        return
    if ex.schedule != 'sparse_delta':
        return
    from repro.kernels import ops as kops
    if not _safa_form(ex).packed:
        init = jax.vmap(protocol.init_aggregate) if fleet \
            else protocol.init_aggregate
        st.agg = init(st.cache, weights)
        return
    spec = _pack_layout(
        _tree_member(st.global_w, 0) if fleet else st.global_w, ex.wire)
    agg = (jax.vmap(protocol.init_aggregate) if fleet
           else protocol.init_aggregate)(st.cache, weights)
    pack_g = kops.pack_stacked if fleet else kops.pack_global
    pack_m = kops.pack_fleet if fleet else kops.pack_stacked

    def scratch(b):
        m = b.shape[-2]
        pad = [(0, 0)] * (b.ndim - 2) + [(0, row_pad(m + 1) - m), (0, 0)]
        return jnp.pad(b, pad)

    st.packed = (pack_g(st.global_w, spec),
                 scratch(pack_m(st.local_w, spec)),
                 scratch(pack_m(st.cache, spec)),
                 pack_g(agg, spec))
    st.spec = spec
    st.local_w = st.cache = None


def _safa_prepare_tier_state(st, weights, ex, fleet: bool, sched):
    """Build the lag-tier carry from the global alone: value buffer
    (capacity + 1 rows of the init global; row ``capacity`` is scratch)
    and the running aggregate ``global * sum(weights)``.  The packed
    buffer is a row slab [R, N/128, 128] (``[S, R, N/128, 128]`` for a
    fleet), so the tier kernels copy exact rows; R is rounded up to whole
    8-row groups, as the sparse engines' [R, N] buffers are, though the
    slab kernels take any R."""
    from repro.kernels import ops as kops
    cap = int(sched.capacity)
    wsum = jnp.sum(weights, axis=-1) if fleet else jnp.sum(weights)

    def scale(g):
        w = wsum.reshape((-1,) + (1,) * (g.ndim - 1)) if fleet else wsum
        return g.astype(jnp.float32) * w

    def rows(g):
        if fleet:
            return jnp.broadcast_to(g[:, None],
                                    (g.shape[0], cap + 1) + g.shape[1:])
        return jnp.broadcast_to(g[None], (cap + 1,) + g.shape)

    if not _safa_form(ex).packed:
        st.cache = jax.tree.map(rows, st.global_w)
        st.agg = jax.tree.map(scale, st.global_w)
        return
    spec = _pack_layout(
        _tree_member(st.global_w, 0) if fleet else st.global_w, ex.wire)
    pack_g = kops.pack_stacked if fleet else kops.pack_global
    gbuf = pack_g(st.global_w, spec)
    cap = row_pad(cap + 1) - 1
    st.packed = (gbuf, rows(kops.as_slab(gbuf)),
                 pack_g(jax.tree.map(scale, st.global_w), spec))
    st.spec = spec


def _sync_precompute(fedcs, form='dense'):
    def precompute(env, sp, *, rounds, seed):
        return federation.precompute_sync_schedule(
            env, fraction=sp.fraction, rounds=rounds, seed=seed, fedcs=fedcs,
            form=form, sampler=getattr(sp, 'sampler', 'choice'))
    return precompute


def _sync_fleet_precompute(fedcs):
    def precompute(members, sp, *, rounds):
        return federation.precompute_sync_fleet_schedule(
            members, rounds=rounds, fedcs=fedcs,
            sampler=getattr(sp, 'sampler', 'choice'))
    return precompute


def _fedavg_prepare_state(st, weights, ex, fleet: bool, sched=None):
    """The stateless sparse-delta FedAvg/FedCS carry is the global model
    alone — drop the [m, ...] local stack before it is ever committed."""
    del weights, fleet, sched
    if ex.schedule == 'sparse_delta':
        st.local_w = None


#: FedAvg/FedCS state forms, by schedule (``sparse_delta`` is stateless)
_FEDAVG_FORMS = {
    'dense': Form('fedavg_run_scan', ('global_w', 'local_w')),
    'sparse': Form('fedavg_run_scan_sparse', ('global_w', 'local_w')),
    'sparse_delta': Form('fedavg_run_scan_sparse_delta', ('global_w',)),
}


def _local_precompute(env, sp, *, rounds, seed):
    return federation.precompute_local_schedule(
        env, fraction=sp.fraction, rounds=rounds, seed=seed)


def _local_fleet_precompute(members, sp, *, rounds):
    del sp
    return schedules.LocalFleetSchedule.stack([
        federation.precompute_local_schedule(
            mem.env, fraction=mem.fraction, rounds=rounds, seed=mem.seed)
        for mem in members])


def _local_finish_segment(st, weights, fleet: bool):
    """There is no global model between rounds — aggregate at eval stops
    (and leave the result in the state so final_global is uniform)."""
    if fleet:
        st.global_w = jax.vmap(protocol.aggregate)(st.local_w, weights)
    else:
        st.global_w = protocol.aggregate(st.local_w, weights)


def _fedasync_precompute(env, sp, *, rounds, seed):
    del seed  # FedAsync's event process draws only from the env rng
    from repro.core import agg_schemes
    return agg_schemes.precompute_async_schedule(
        env, rounds=rounds, **agg_schemes.async_kwargs(sp))


def _fedasync_fleet_precompute(members, sp, *, rounds):
    from repro.core import agg_schemes
    return schedules.AsyncFleetSchedule.stack([
        agg_schemes.precompute_async_schedule(
            mem.env, rounds=rounds, **agg_schemes.async_kwargs(sp, mem))
        for mem in members])


def _safa_dispatch_budget(ex) -> Optional[int]:
    """Pallas dispatches per compiled SAFA round (verified statically by
    ``repro.analysis`` JAX001 against the lowered scan body).  The dense/
    sparse int8 cells are the PR 4 invariant: a fully compressed round is
    exactly 2 dispatches (quantize + fused q8 aggregate) however many
    leaves the model has."""
    if ex.schedule == 'sparse_tier':
        if not ex.use_kernel:
            return 2 if ex.wire == 'int8' else 0
        # gather bases + fused tier aggregate (+ quantize on the wire)
        return 3 if ex.wire == 'int8' else 2
    if ex.schedule == 'sparse_delta':
        if not ex.use_kernel:
            return 2 if ex.wire == 'int8' else 0
        # gather + rows aggregate + scatter x2 (local rows, cache rows)
        return 5 if ex.wire == 'int8' else 4
    if ex.wire == 'int8':
        return 2
    if ex.use_kernel == 'packed':
        return 1
    if ex.use_kernel:
        return None     # leaf-wise: one dispatch per pytree leaf
    return 0


def _safa_alias_claims(ex) -> dict:
    """In-place aliases the cell's lowered program must carry (JAX003):
    dropping any of these silently doubles the server's resident cache/
    buffer footprint."""
    if ex.schedule == 'sparse_tier':
        if not ex.use_kernel:
            return {}
        return ({'_q8_tier_rows_kernel': ((5, 2),)} if ex.wire == 'int8'
                else {'_tier_rows_kernel': ((2, 2),)})
    if ex.schedule == 'sparse_delta':
        if not ex.use_kernel:
            return {}
        return {'_scatter_kernel': ((2, 0),)}
    if ex.wire == 'int8':
        return {'_q8_kernel': ((3, 1),)}
    if ex.use_kernel == 'packed':
        return {'_kernel': ((0, 1),)}
    return {}


def _wire_only_dispatch_budget(ex) -> int:
    """Kernel-less protocols touch pallas only through the int8 wire
    round-trip (quantize + dequantize)."""
    return 2 if ex.wire == 'int8' else 0


register(ProtocolDef(
    name='safa', spec_cls=SafaSpec,
    precompute=_safa_precompute,
    fleet_precompute=lambda members, sp, *, rounds:
        federation.precompute_fleet_schedule(members, rounds=rounds),
    form=_safa_form, uses_cache=True, supports_wire=True,
    supports_kernel=True,
    sparse_precompute=_safa_sparse_precompute,
    prepare_state=_safa_prepare_state,
    tier_precompute=_safa_tier_precompute,
    dispatch_budget=_safa_dispatch_budget,
    alias_claims=_safa_alias_claims))

register(ProtocolDef(
    name='fedavg', spec_cls=FedAvgSpec,
    precompute=_sync_precompute(fedcs=False),
    fleet_precompute=_sync_fleet_precompute(fedcs=False),
    form=lambda ex: _FEDAVG_FORMS[ex.schedule], supports_wire=True,
    sparse_precompute=_sync_precompute(fedcs=False, form='sparse'),
    prepare_state=_fedavg_prepare_state, delta_stateless=True,
    dispatch_budget=_wire_only_dispatch_budget))

register(ProtocolDef(
    name='fedcs', spec_cls=FedCSSpec,
    precompute=_sync_precompute(fedcs=True),
    fleet_precompute=_sync_fleet_precompute(fedcs=True),
    form=lambda ex: _FEDAVG_FORMS[ex.schedule], supports_wire=True,
    sparse_precompute=_sync_precompute(fedcs=True, form='sparse'),
    prepare_state=_fedavg_prepare_state, delta_stateless=True,
    dispatch_budget=_wire_only_dispatch_budget))

register(ProtocolDef(
    name='local', spec_cls=LocalSpec,
    precompute=_local_precompute,
    fleet_precompute=_local_fleet_precompute,
    form=lambda ex: Form('local_run_scan', ('local_w',)),
    finish_segment=_local_finish_segment,
    dispatch_budget=lambda ex: 0))

register(ProtocolDef(
    name='fedasync', spec_cls=FedAsyncSpec,
    precompute=_fedasync_precompute,
    fleet_precompute=_fedasync_fleet_precompute,
    form=lambda ex: Form('fedasync_run_scan', ('global_w', 'local_w')),
    spec_overrides=True,
    dispatch_budget=lambda ex: 0))


# ---------------------------------------------------------------------------
# Experiment + CompiledRunner
# ---------------------------------------------------------------------------

class Experiment:
    """One declarative experiment: (task, env, protocol spec, exec spec,
    rounds, seed).  ``task`` may be None for timing-only runs
    (``ExecSpec(numeric=False)``).

    ``env`` is declarative too: pass an ``fedsim.EnvSpec`` and the
    experiment builds it (validated in ``check_compat``; wire-derived
    comm sizes injected under ``comm='wire'``).  A pre-built ``Env`` (or
    the deprecated ``FLEnv``) is accepted unchanged."""

    def __init__(self, task, env, protocol: ProtocolSpec,
                 exec: Optional[ExecSpec] = None, *,  # noqa: A002
                 rounds: int, seed: int = 0):
        self.task = task
        self.protocol = protocol
        self.exec = exec if exec is not None else ExecSpec()
        self.rounds = int(rounds)
        self.seed = int(seed)
        self._pdef = check_compat(self.protocol, self.exec, env=env)
        self.env = _realize_env(env, task=task, ex=self.exec)
        self._sched = None

    def precompute(self):
        """Run the host event state machine (versions, crash draws,
        selection) once and cache the schedule — [rounds, m] masks for
        ``schedule='dense'``, native [rounds, quota] (idx, roles) tensors
        otherwise (same event stream, O(m + rounds*quota) host memory).
        The env rng is consumed exactly once per Experiment — repeated
        calls (and repeated ``run()``s) replay the same schedule."""
        if self._sched is None:
            if self.exec.schedule == 'dense':
                pre = self._pdef.precompute
            elif self.exec.schedule == 'sparse_tier':
                pre = self._pdef.tier_precompute
            else:
                pre = self._pdef.sparse_precompute
            with obs.span('precompute'):
                self._sched = pre(self.env, self.protocol,
                                  rounds=self.rounds, seed=self.seed)
        return self._sched

    def compile(self) -> 'CompiledRunner':
        """Resolve the engine and pin the static pieces of the compiled
        program (train fn, kernel/wire modes).  The XLA trace itself is
        built at the first ``run()`` dispatch and cached by jit."""
        return CompiledRunner(self)

    def fingerprint(self, members=None, tasks=None, task=None) -> str:
        """Identity of the run a checkpoint belongs to: protocol/exec
        specs, rounds, seed, env(s) — and the task(s), so a carry is
        never resumed against different training data."""
        parts = [
            f'proto={self._pdef.name}',
            f'spec={dataclasses.asdict(self.protocol)!r}',
            f'exec={dataclasses.asdict(self.exec)!r}',
            f'rounds={self.rounds}', f'seed={self.seed}',
        ]
        if members is None:
            parts.append('env=' + _env_fp(self.env))
            parts.append('task=' + _task_fp(self.task))
        else:
            parts += ['member=' + _env_fp(mem.env) + repr(
                (mem.fraction, mem.lag_tolerance, mem.seed, mem.alpha,
                 mem.staleness_exp, mem.overrides)) for mem in members]
            if tasks is not None:
                parts += ['task=' + _task_fp(t) for t in tasks]
            else:
                parts.append('task=' + _task_fp(task))
        return '|'.join(parts)


def _carry_of(st: _RunState, form: Form) -> tuple:
    if form.packed:
        return st.packed
    return tuple(getattr(st, field) for field in form.carry)


def _set_carry(st: _RunState, form: Form, carry: tuple, fleet: bool):
    if form.packed:
        from repro.kernels import ops as kops
        st.packed = carry
        unpack = kops.unpack_stacked if fleet else kops.unpack_global
        st.global_w = unpack(carry[0], st.spec)
    else:
        for field, tree in zip(form.carry, carry):
            setattr(st, field, tree)


def _run_segment(form: Form, engine: str, st: _RunState, seg, weights,
                 train_fn, ex: ExecSpec, extra=()):
    """Advance ``st`` through the rounds of ``seg``, a slice of the device
    schedule, in ``form``.  ``engine='scan'`` makes one call of the
    form's engine, ``'fleet'`` one call of its fleet twin (state, ``seg``,
    ``weights`` and ``extra`` member-stacked), and ``'loop'`` applies the
    form's step to each row in turn, eagerly, op by op.  ``extra``
    reaches every train call after the round index."""
    static = dict(local_train_fn=train_fn, use_kernel=ex.use_kernel,
                  wire=ex.wire, spec=st.spec)
    if engine == 'loop':
        step = protocol.ENGINES[form.engine].step
        for t in range(len(seg.round_idx)):
            row = jax.tree.map(lambda a, t=t: a[t], seg)
            _set_carry(st, form, step(_carry_of(st, form), row, weights,
                                      extra, **static), False)
        return
    fleet = engine == 'fleet'
    name = protocol.ENGINES[form.engine].fleet if fleet else form.engine
    carry = _carry_of(st, form)
    out = getattr(protocol, name)(*carry, seg, weights, extra=extra,
                                  **static)
    _set_carry(st, form, out if len(carry) > 1 else (out,), fleet)


def _sharded_segment(form, train_fn, ex, extra, spec, sharding):
    """The fleet engine of ``_run_segment`` on every device of
    ``sharding``'s mesh at once, each device running the unsharded
    segment program on the members it holds; the outputs are reassembled
    into fleet arrays sharded like the inputs.

    Not one partitioned program: Mosaic kernels cannot be partitioned
    automatically, and a fleet traced over a mesh (jit, shard_map or pmap
    alike) carries the mesh into its types, which JAX's batching rule for
    grouped convolutions — a CNN's weight gradient, vmapped over clients
    and then over members — refuses.  JAX dispatches asynchronously, so
    the devices run concurrently; each compiles its own copy."""
    devices = list(sharding.mesh.devices.flat)

    def run(st, seg, weights):
        leaves, treedef = jax.tree.flatten((st.tree(), seg, weights, extra))
        by_device = [{sh.device: sh.data for sh in leaf.addressable_shards}
                     for leaf in leaves]
        outs = []
        for d in devices:
            tree, seg_d, w_d, extra_d = jax.tree.unflatten(
                treedef, [shards[d] for shards in by_device])
            local = _RunState()
            local.set_tree(tree)
            local.spec = spec
            _run_segment(form, 'fleet', local, seg_d, w_d, train_fn, ex,
                         extra_d)
            outs.append(local.tree())
        st.set_tree(jax.tree.map(
            lambda *parts: jax.make_array_from_single_device_arrays(
                (sum(p.shape[0] for p in parts),) + parts[0].shape[1:],
                sharding, list(parts)),
            *outs))
    return run


class CompiledRunner:
    """Executes an ``Experiment``.  ``run()`` drives the single
    simulation; ``run_sweep(members)`` drives S member configurations as
    one batched fleet.  Both checkpoint at eval-segment boundaries when
    ``checkpoint=`` names a path, and resume from it when it exists."""

    def __init__(self, exp: Experiment):
        self.exp = exp
        self._pdef = exp._pdef
        self._form = exp._pdef.form(exp.exec)
        self._dev = None            # cached device-resident schedule
        self._operand_train = None  # _operand_train_fn, made once
        #: after a fleet-engine ``run_sweep``: the members' final global
        #: models stacked [S', ...], device-resident with the placement
        #: the sweep ran on (sharded over the fleet axis across devices;
        #: S' > S when the fleet was padded, rows past S copy member S-1)
        self.fleet_global = None

    # -- single run ---------------------------------------------------------

    def _engine(self, *, sweep: bool) -> str:
        e = self.exp.exec.engine
        if sweep:
            e = e if e is not None else 'fleet'
            if e not in ('fleet', 'sequential'):
                raise ValueError(
                    f'unknown engine {e!r} (want "fleet" or "sequential")')
        else:
            e = e if e is not None else 'scan'
            if e not in ('scan', 'loop'):
                raise ValueError(
                    f'unknown engine {e!r} (want "scan" or "loop")')
        return e

    def _stateless(self, ex) -> bool:
        """Global-only carry: skip the [m, ...] local/cache stacks.

        Lag-tier runs are always stateless here — ``prepare_state`` then
        builds the O(tau+quota) value buffer in the cache slot."""
        return (ex.schedule == 'sparse_delta' and self._pdef.delta_stateless) \
            or ex.schedule == 'sparse_tier'

    def _train_fn(self, task):
        quantized = getattr(self.exp.protocol, 'quantize_uploads', False)
        if _operands_of(task) is not None:
            # one callable a runner, so that every run hits its compile
            if self._operand_train is None:
                fn = _operand_train_fn(task)
                self._operand_train = (federation._quantized_train_fn(fn)
                                       if quantized else fn)
            return self._operand_train
        if self.exp.exec.schedule != 'dense':
            # rows-train contract: (params_rows, rows, round_idx)
            return task.local_train_rows
        if quantized:
            return federation._quantized_train_fn(task.local_train)
        return task.local_train

    def run(self, *, checkpoint: Optional[str] = None,
            max_segments: Optional[int] = None) -> History:
        """Execute the experiment.  ``checkpoint`` (a path) enables
        save/resume at eval-segment boundaries; ``max_segments`` stops
        after that many segments *this call* (the partial History carries
        the state reached so far — resume via ``checkpoint``)."""
        exp = self.exp
        ex = exp.exec
        engine = self._engine(sweep=False)
        sched = exp.precompute()
        hist = History(self._pdef.name, records=_fresh_records(sched.records),
                       futility=sched.futility)
        if not ex.numeric:
            return hist
        if exp.task is None:
            raise ValueError('numeric run needs a Task '
                             '(or ExecSpec(numeric=False))')

        operands = _operands_of(exp.task)
        if operands is not None and (
                self._pdef.name != 'safa' or ex.schedule != 'dense'
                or engine != 'scan'):
            raise ValueError(
                'a task with device operands (Task.operands) runs on the '
                "dense SAFA scan engine only (schedule='dense', "
                f"engine='scan'); got {self._pdef.name!r}, "
                f'schedule={ex.schedule!r}, engine={engine!r}')
        with obs.span('run.prepare'):
            st = _init_state(exp.task, exp.env.m, exp.seed,
                             self._pdef.uses_cache, self._stateless(ex))
            weights_j = jnp.asarray(exp.env.weights)
            if self._pdef.prepare_state is not None:
                self._pdef.prepare_state(st, weights_j, ex, False, sched)
            if self._dev is None:
                self._dev = sched.to_device()
        start_seg = 0
        fingerprint = exp.fingerprint()
        if checkpoint is not None and ckpt.exists(checkpoint):
            tree, start_seg, saved = ckpt.load_run(
                checkpoint, st.tree(), fingerprint=fingerprint)
            st.set_tree(tree)
            _apply_saved_history(hist, saved[0])

        weights = weights_j
        train_fn = self._train_fn(exp.task)
        extra = () if operands is None else (operands,)
        evals = _eval_rounds(exp.rounds, ex.eval_every)
        start = evals[start_seg - 1] if start_seg else 0
        done = 0
        for k in range(start_seg, len(evals)):
            stop = evals[k]
            with obs.span('segment'):
                seg = jax.tree.map(
                    lambda a, s=start, e=stop: a[s:e], self._dev)
                _run_segment(self._form, engine, st, seg, weights, train_fn,
                             ex, extra)
                if self._pdef.finish_segment is not None:
                    self._pdef.finish_segment(st, weights, False)
            _record_eval(hist, hist.records[stop - 1], exp.task, st.global_w)
            start = stop
            done += 1
            if checkpoint is not None:
                ckpt.save_run(checkpoint, st.tree(), seg_done=k + 1,
                              histories=[hist], fingerprint=fingerprint)
            if max_segments is not None and done >= max_segments \
                    and k + 1 < len(evals):
                break
        hist.final_global = st.global_w
        return hist

    # -- sweeps -------------------------------------------------------------

    def run_sweep(self, members, *, checkpoint: Optional[str] = None,
                  max_segments: Optional[int] = None) -> list:
        """Run S = len(members) simulations of this protocol as a batched
        fleet; returns one ``History`` per member, in order.

        ``members`` is a list of ``SweepMember`` or a ``SweepSpec``; a
        ``SweepSpec`` may carry per-member ``tasks`` (padded stacking —
        members may then hold different client partitions).  The
        experiment's own env/seed are not used here; each member carries
        its own.  ``engine='fleet'`` (default) executes all members in a
        single vmapped-scan dispatch per eval segment (sharded over JAX
        devices when several are visible, the fleet padded with copies
        of its last member to a multiple of the device count);
        ``engine='sequential'`` drives the same precomputed schedules
        through S per-member scan runs — bit-identical per member."""
        exp = self.exp
        ex = exp.exec
        engine = self._engine(sweep=True)
        if isinstance(members, SweepSpec):
            sweep, members = members, list(members.members)
            tasks = list(sweep.tasks) if sweep.tasks is not None else None
        else:
            members, tasks = list(members), None
        if not members:
            raise ValueError('empty sweep')
        if _operands_of(exp.task) is not None:
            raise ValueError('a task with device operands (Task.operands) '
                             'runs through run(), not run_sweep()')
        # resolve declarative member envs up front: split env-field
        # overrides from protocol overrides, apply them to the EnvSpec,
        # and build each member its own Env (one fleet dispatch may then
        # mix crash rates, traces, device-class grids, ...)
        members = [
            _resolve_member(mem, pdef=self._pdef, ex=ex,
                            task=tasks[s] if tasks is not None else exp.task)
            for s, mem in enumerate(members)]
        m = members[0].env.m
        if any(mem.env.m != m for mem in members):
            raise ValueError('fleet members must share the client count m')
        if tasks is not None and all(t is tasks[0] for t in tasks):
            # one shared task object: take the cheaper no-padding path
            shared_task, tasks = tasks[0], None
        else:
            shared_task = exp.task
        if getattr(exp.protocol, 'quantize_uploads', False):
            raise ValueError(
                'quantize_uploads is the single-run per-leaf reference '
                "knob; sweeps take the packed wire instead (wire='int8')")
        if ex.schedule != 'dense' and tasks is not None:
            raise ValueError(
                'sparse schedules need the rows-train contract, which the '
                'padded per-member task stack does not implement; use a '
                'shared task (or schedule="dense")')

        fleet = self._pdef.fleet_precompute(members, exp.protocol,
                                            rounds=exp.rounds)
        if ex.schedule == 'sparse_tier':
            # fleet-major lag-tier form of the SAME event stream: member
            # slot maps are remapped into the shared fleet-max capacity
            fleet = fleet.to_tier()
        elif ex.schedule != 'dense':
            # fleet-major sparse form of the SAME event stream (members
            # re-padded to the fleet-max active-set capacity)
            fleet = fleet.to_sparse()
        hists = [History(self._pdef.name,
                         records=_fresh_records(fleet.records[s]),
                         futility=float(fleet.futility[s]))
                 for s in range(fleet.size)]
        if not ex.numeric:
            return hists
        if shared_task is None and tasks is None:
            raise ValueError('numeric sweep needs a Task (shared or '
                             'per-member) or ExecSpec(numeric=False)')
        if checkpoint is not None and engine != 'fleet':
            raise ValueError("sweep checkpointing requires engine='fleet'")

        weights = jnp.asarray(np.stack([mem.env.weights for mem in members]))
        evals = _eval_rounds(exp.rounds, ex.eval_every)

        if engine == 'sequential':
            for s, (mem, hist) in enumerate(zip(members, hists)):
                task_s = tasks[s] if tasks is not None else shared_task
                st = _init_state(task_s, m, mem.seed, self._pdef.uses_cache,
                                 self._stateless(ex))
                msched = fleet.member(s)
                dev = msched.to_device()
                w_s = jnp.asarray(mem.env.weights)
                train_fn = task_s.local_train if ex.schedule == 'dense' \
                    else task_s.local_train_rows
                if self._pdef.prepare_state is not None:
                    self._pdef.prepare_state(st, w_s, ex, False, msched)
                start = 0
                for stop in evals:
                    seg = jax.tree.map(
                        lambda a, s=start, e=stop: a[s:e], dev)
                    _run_segment(self._form, 'scan', st, seg, w_s,
                                 train_fn, ex)
                    if self._pdef.finish_segment is not None:
                        self._pdef.finish_segment(st, w_s, False)
                    _record_eval(hist, hist.records[stop - 1], task_s,
                                 st.global_w)
                    start = stop
                hist.final_global = st.global_w
            return hists

        # fleet engine: one init per member (deduped per distinct seed for
        # a shared task — vmapping init_global is NOT bit-stable), then one
        # broadcast into the fleet-major carry
        if tasks is not None:
            stacked = _stacked_task(tasks)
            extra = (stacked.fleet_ctx(),)
            train_fn = stacked.fleet_train
            g = _stack_trees([tasks[s].init_global(jax.random.PRNGKey(mem.seed))
                              for s, mem in enumerate(members)])
        else:
            extra = ()
            train_fn = self._train_fn(shared_task)
            g = init_fleet_global(shared_task, [mem.seed for mem in members])

        def bcast():
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[:, None],
                                           (a.shape[0], m) + a.shape[1:]), g)

        if self._stateless(ex):
            st = _RunState(g, None, None)
        else:
            st = _RunState(g, bcast(),
                           bcast() if self._pdef.uses_cache else None)
        if self._pdef.prepare_state is not None:
            self._pdef.prepare_state(st, weights, ex, True, fleet)
        start_seg = 0
        fingerprint = exp.fingerprint(members, tasks=tasks, task=shared_task)
        if checkpoint is not None and ckpt.exists(checkpoint):
            tree, start_seg, saved = ckpt.load_run(
                checkpoint, st.tree(), fingerprint=fingerprint)
            st.set_tree(tree)
            for hist, d in zip(hists, saved):
                _apply_saved_history(hist, d)

        dev = fleet.to_device()
        size = len(members)
        ndev = len(jax.devices())
        form = self._form
        segment = lambda st_, seg_, w_: _run_segment(
            form, 'fleet', st_, seg_, w_, train_fn, ex, extra)
        if ex.shard and ndev > 1:
            # every device holds an equal share of the fleet: pad it with
            # copies of the last member up to a multiple of the device
            # count (their results are never read or saved)
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            pad = -size % ndev
            tree, dev, weights, extra = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.repeat(a[-1:], pad, axis=0)]) if pad else a,
                (st.tree(), dev, weights, extra))
            mesh = Mesh(np.asarray(jax.devices()), ('fleet',))
            sharding = NamedSharding(mesh, PartitionSpec('fleet'))
            tree, dev, weights, extra = jax.device_put(
                (tree, dev, weights, extra), sharding)
            st.set_tree(tree)
            segment = _sharded_segment(form, train_fn, ex, extra, st.spec,
                                       sharding)

        start = evals[start_seg - 1] if start_seg else 0
        done = 0
        g_host = jax.tree.map(np.asarray, st.global_w)
        for k in range(start_seg, len(evals)):
            stop = evals[k]
            seg = jax.tree.map(
                lambda a, s=start, e=stop: a[:, s:e], dev)
            segment(st, seg, weights)
            if self._pdef.finish_segment is not None:
                self._pdef.finish_segment(st, weights, True)
            # one host gather per leaf: slicing members out of a (possibly
            # device-sharded) fleet array S times is far slower than one
            # fetch + S host slices
            g_host = jax.tree.map(np.asarray, st.global_w)
            for s, hist in enumerate(hists):
                task_s = tasks[s] if tasks is not None else shared_task
                _record_eval(hist, hist.records[stop - 1], task_s,
                             _tree_member(g_host, s))
            start = stop
            done += 1
            if checkpoint is not None:
                ckpt.save_run(checkpoint,
                              jax.tree.map(lambda a: a[:size], st.tree()),
                              seg_done=k + 1, histories=hists,
                              fingerprint=fingerprint)
            if max_segments is not None and done >= max_segments \
                    and k + 1 < len(evals):
                break
        for s, hist in enumerate(hists):
            hist.final_global = _tree_member(g_host, s)
        self.fleet_global = st.global_w
        return hists
