"""One cell from its files: the configuration's task, the mix's protocol,
wire and schedule, read in this one place for every cell.

The task module (``bench/tasks/<config.task>.py``) makes the data, the
weights and the program's task from ``--seed``, and the reference's
training; the protocol module (``bench/protocols/<mix.protocol>.py``)
gives the program's spec and the reference's event process and rounds.
A key of the mix or of the configuration's ``protocol`` group that the
reference cannot replay is refused here, before anything runs.
"""
from __future__ import annotations

import importlib

import jax
import numpy as np

from bench import reference, traffic
from bench.adapter import Adapter
from bench.cell import Cell, Shape


class SpecError(ValueError):
    """A workload, configuration, mix or metric that the files lack, or
    that the reference cannot replay."""


#: integer levels a side of each uplink wire; 0 is the f32 wire
WIRES = {'f32': 0, 'int8': 127}
#: the wire a step below each, the control of a cell that states it
CONTROL_WIRE = {127: 7}             # int8 -> int4
MIX_KEYS = {'name', 'loop', 'protocol', 'rounds', 'exec'}
EXEC_KEYS = {'use_kernel', 'schedule', 'wire', 'eval_every'}
#: faults every protocol's reference can have planted
PROTOCOL_FAULTS = ('half_uploads',)


def _module(kind: str, name: str):
    try:
        return importlib.import_module(f'bench.{kind}.{name}')
    except ModuleNotFoundError as e:
        raise SpecError(f'no module bench/{kind}/{name}.py') from e


def task_module(config: dict):
    if 'task' not in config:
        raise SpecError('the configuration names no task')
    return _module('tasks', config['task'])


def protocol_of(config: dict, mix: dict):
    """The mix's protocol module and the configuration's parameters of
    it, checked against what the reference replays."""
    extra = set(mix) - MIX_KEYS
    if extra:
        raise SpecError(f'mix keys the reference cannot replay: '
                        f'{sorted(extra)}')
    ex = mix.get('exec', {})
    extra = set(ex) - EXEC_KEYS
    if extra:
        raise SpecError(f'exec keys the reference cannot replay: '
                        f'{sorted(extra)}')
    if ex.get('wire', 'f32') not in WIRES:
        raise SpecError(f'no reference for wire {ex["wire"]!r}')
    if mix['rounds'] % ex['eval_every']:
        raise SpecError('a mix runs whole segments: rounds must be a '
                        'multiple of eval_every')
    if 'protocol' not in mix:
        raise SpecError('the mix names no protocol')
    proto = _module('protocols', mix['protocol'])
    if ex.get('schedule', 'dense') not in proto.SCHEDULES:
        raise SpecError(f'the {mix["protocol"]} reference replays no '
                        f'schedule {ex["schedule"]!r}')
    params = config.get('protocol', {})
    if set(params) != set(proto.PARAMS):
        raise SpecError(f'the {mix["protocol"]} protocol takes '
                        f'{sorted(proto.PARAMS)}; the configuration gives '
                        f'{sorted(params)}')
    return proto, params


def build(spec: dict, seed: int) -> Cell:
    from repro import api

    config, mix = spec['config'], spec['mix']
    proto, params = protocol_of(config, mix)
    parts = task_module(config).build(config, seed)
    adapter = Adapter(parts.program, parts.init, traffic.seed_key(seed))
    exp = api.Experiment(adapter, parts.env_spec, proto.api_spec(params),
                         api.ExecSpec(**mix['exec']), rounds=mix['rounds'],
                         seed=0)
    first = mix['exec']['eval_every']
    levels = WIRES[mix['exec'].get('wire', 'f32')]
    memo = {}

    def replay(*, lower=False, wire=levels, fault=None):
        """The reference's global after the first segment."""
        if 'draws' not in memo:
            draws = traffic.env_draws(parts.env_spec, first)
            masks = proto.masks(draws, params, first)
            clients = np.flatnonzero(masks.active())
            weights = np.asarray(draws.weights, np.float64)
            rest = float(weights.sum() - weights[clients].sum())
            memo['draws'] = (masks.take(clients), clients,
                             weights[clients], rest)
            memo['start'] = jax.device_get(
                jax.jit(parts.init)(traffic.seed_key(seed)))
        masks, clients, weights, rest = memo['draws']
        if fault == 'half_uploads':
            weights = reference.half_weights(weights)
        outs = [proto.replay(start, masks, weights, first, train=train,
                             aux=aux, levels=wire, rest=rest)
                for start, train, aux in parts.pieces(
                    memo['start'], clients, lower=lower,
                    fault=fault if fault in parts.faults else None)]
        return parts.join(outs)

    def want():
        if 'want' not in memo:
            memo['want'] = jax.device_get(replay())
        return memo['want']

    def check(got) -> dict:
        gap, per_leaf, left = reference.change_gap(got, want(),
                                                   memo['start'])
        return {'change_gap': gap, 'leaf_gaps': per_leaf,
                'leaves_left_out': left}

    stand_ins = ('control',) + PROTOCOL_FAULTS + tuple(parts.faults)

    def stand_in(name: str):
        if name == 'control':
            if levels in CONTROL_WIRE:
                return replay(wire=CONTROL_WIRE[levels])
            return replay(lower=True)
        if name not in stand_ins:
            raise SpecError(f'no stand-in {name!r}; this cell has '
                            f'{list(stand_ins)}')
        return replay(fault=name)

    def shape() -> Shape:
        rounds = mix['rounds']
        masks = proto.masks(traffic.env_draws(parts.env_spec, rounds),
                            params, rounds)
        return Shape(m=parts.env_spec.m, n=parts.n,
                     flops_per_client=parts.flops_per_client,
                     bytes_per_client=parts.bytes_per_client,
                     committed=masks.uploads(), rows_read=masks.rows_read(),
                     rows_written=masks.rows_written())

    return Cell(experiment=exp, adapter=adapter, rounds=mix['rounds'],
                eval_every=first, check=check, stand_in=stand_in,
                stand_ins=stand_ins, shape=shape)
