"""The reference's SAFA event process against the program's precompute:
the same draws must give the same role masks, round by round."""
import numpy as np
import pytest

from bench import events, traffic
from bench.tasks.scale import make_scale_env


def program_masks(env_spec, fraction, tau, rounds):
    from repro.core import federation
    return federation.precompute_safa_schedule(
        env_spec.build(), fraction=fraction, lag_tolerance=tau,
        rounds=rounds)


def paper_like(m, crash, seed, draw_seed, t_lim):
    from repro.fedsim import EnvSpec
    return EnvSpec(m=m, crash_prob=crash, dataset_size=700 * m,
                   batch_size=40, epochs=5, t_lim=t_lim, seed=seed,
                   draw_seed=draw_seed)


@pytest.mark.parametrize('m,crash,fraction,tau,t_lim,draw_seed', [
    (100, 0.3, 0.3, 5, 5600.0, 3),
    (100, 0.3, 0.3, 5, 5600.0, 2**31 + 11),
    (50, 0.5, 0.1, 2, 3000.0, 9),
    (200, 0.0, 0.05, 3, 2000.0, 4),
])
def test_masks_match_program(m, crash, fraction, tau, t_lim, draw_seed):
    rounds = 30
    spec = paper_like(m, crash, 0, draw_seed, t_lim)
    want = program_masks(spec, fraction, tau, rounds)
    got = events.safa_masks(traffic.env_draws(spec, rounds),
                            fraction=fraction, lag_tolerance=tau,
                            rounds=rounds)
    for k in ('sync', 'committed', 'picked', 'undrafted', 'deprecated'):
        np.testing.assert_array_equal(getattr(got, k), getattr(want, k),
                                      err_msg=k)
    assert got.committed.any() and (~got.committed).any()


def test_quota_bounded_env_matches_program():
    spec = make_scale_env(20_000, 20, 0, 7)
    rounds = 12
    want = program_masks(spec, 20 / 20_000, 1000, rounds)
    got = events.safa_masks(traffic.env_draws(spec, rounds),
                            fraction=20 / 20_000, lag_tolerance=1000,
                            rounds=rounds)
    for k in ('sync', 'committed', 'picked', 'undrafted', 'deprecated'):
        np.testing.assert_array_equal(getattr(got, k), getattr(want, k),
                                      err_msg=k)
    # about 2.5x the quota complete each round after the first
    assert 30 <= got.committed[1:].sum(axis=1).mean() <= 70


def test_replay_over_the_active_clients_is_the_dense_replay():
    """The clients with no part in the replayed rounds hold the initial
    model throughout: one weight on it in Eq. 7 stands for them all."""
    import functools

    import jax.numpy as jnp

    from bench import reference
    from bench.tasks import scale
    m, rounds, d = 2000, 6, 512
    spec = make_scale_env(m, 10, 0, 7)
    draws = traffic.env_draws(spec, rounds)
    masks = events.safa_masks(draws, fraction=10 / m, lag_tolerance=1000,
                              rounds=rounds)
    active = np.flatnonzero(masks.active())
    assert 0 < len(active) < m // 10
    train = functools.partial(scale.ref_train, lr=0.3, salt=jnp.uint32(9))
    start = {'w': 0.01 * np.arange(d, dtype=np.float32) / d}
    cols = jnp.arange(d, dtype=jnp.int32)
    w = np.asarray(draws.weights)
    full = reference.replay(start, masks, w, rounds, train=train,
                            aux=(jnp.arange(m, dtype=jnp.int32), cols),
                            levels=127)
    part = reference.replay(start, masks.take(active), w[active], rounds,
                            train=train,
                            aux=(jnp.asarray(active, jnp.int32), cols),
                            levels=127, rest=w.sum() - w[active].sum())
    change = np.linalg.norm(np.asarray(full['w']) - start['w'])
    assert change > 0
    assert np.linalg.norm(np.asarray(part['w']) - np.asarray(full['w'])) \
        < 1e-4 * change
