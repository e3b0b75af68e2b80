"""Traffic as the plain reference sees it: what a fresh build of the
cell's environment draws, as plain arrays (``events.Draws``).

Each build of an ``EnvSpec`` draws the same population and event stream,
so these are the draws the experiment's own build consumed.
"""
from __future__ import annotations

import numpy as np

from bench.events import Draws


def env_draws(env_spec, rounds: int) -> Draws:
    if env_spec.comm != 'static' or env_spec.traces is not None:
        raise ValueError('the reference replays static environments only')
    env = env_spec.build()
    timing = env.round_timing(rounds)
    crashed, frac = env.draw_rounds(rounds)
    return Draws(t_up=timing.t_up, t_down=timing.t_down,
                 full_tt=timing.full_tt, crashed=crashed, crash_frac=frac,
                 weights=np.asarray(env.weights), t_lim=float(env.t_lim),
                 dist_mb=float(env.model_size_mb),
                 server_bw_mbps=float(env.server_bw_mbps))


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words drawn from any whole-number seed."""
    return np.random.SeedSequence(seed).generate_state(n)


def seed_key(seed: int):
    """A JAX PRNG key from any whole-number seed (``PRNGKey`` keeps only
    32 bits of an int)."""
    import jax
    import jax.numpy as jnp
    return jax.random.wrap_key_data(jnp.asarray(seed_words(seed, 2)),
                                    impl='threefry2x32')
