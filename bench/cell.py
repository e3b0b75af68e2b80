"""What a task module hands the harness, and what the harness builds of
it for one cell."""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np


@dataclasses.dataclass
class TaskParts:
    """One configuration's task, made from ``--seed``: the program's side
    and the plain reference's side."""
    program: object             # the program's Task, as api.Experiment takes it
    init: Callable              # key -> the initial weights (a pytree)
    env_spec: object            # fedsim.EnvSpec of the population
    n: int                      # model parameters
    flops_per_client: float     # training FLOPs of one client in one round
    bytes_per_client: float     # data bytes one client's training reads a round
    #: ``pieces(start, clients, lower=, fault=)`` -> [(start_piece, train,
    #: aux)]: the reference's rounds split into parts that replay alone
    #: (``train(base, t, aux, committed)`` of ``reference.safa_round``),
    #: for the clients ``clients``; ``lower`` computes in the precision
    #: below the configuration's, ``fault`` names one of ``faults``
    pieces: Callable
    join: Callable              # [global piece] -> the global model
    #: faults the task can plant in its reference (beside the protocol's)
    faults: tuple = ()


@dataclasses.dataclass(frozen=True)
class Shape:
    """Logical shapes of the cell's rounds: the required work the
    per-layer readers count from."""
    m: int                      # clients
    n: int                      # model parameters
    flops_per_client: float
    bytes_per_client: float
    committed: np.ndarray       # [rounds] clients whose upload is used
    rows_read: np.ndarray       # [rounds] local models training starts from
    rows_written: np.ndarray    # [rounds] cache entries that change


@dataclasses.dataclass
class Cell:
    experiment: object              # api.Experiment, ready to compile
    adapter: object                 # adapter.Adapter the experiment trains
    rounds: int                     # rounds of one run
    eval_every: int                 # rounds of one segment
    #: ``check(got) -> {name: reading}``: ``got``, the global after the
    #: first segment, against the plain reference (``change_gap``)
    check: Callable
    #: ``stand_in(name) -> global``: the reference with one change, put
    #: in the program's place: ``'control'`` (the precision below the
    #: configuration's) or a fault of ``stand_ins``
    stand_in: Callable
    stand_ins: tuple
    #: () -> Shape, from the protocol's event masks over a whole run
    shape: Callable
