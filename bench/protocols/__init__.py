"""Protocols, one module per ``protocol`` named in a mix file.

Each exposes ``SCHEDULES`` (the ``ExecSpec.schedule`` forms whose rounds
its reference replays), ``PARAMS`` (the keys it reads from the
configuration's ``protocol`` group), ``api_spec(params)`` (the program's
spec), ``masks(draws, params, rounds)`` (its event process, written from
the paper over the environment's draws) and ``replay(...)`` (its plain
rounds, with the signature of ``bench.reference.replay``).
"""
