"""End-to-end federated training driver (silo-mode SAFA).

Runs a real (reduced-size, CPU-feasible) federated LLM training: the SAFA
protocol drives per-round client states from the event simulator, while the
numeric round executes as one jit-ed ``SiloSetup.train_step`` on the local
mesh.  On real hardware the identical code runs on the production mesh.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --rounds 50 --clients 4 --fraction 0.5 --lag-tolerance 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.core import protocol, selection
from repro.data import make_lm_tokens
from repro.fedsim import EnvSpec
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import SiloSetup
from repro.models.model import build_model


def run(arch: str, *, rounds: int, n_clients: int, fraction: float,
        lag_tolerance: int, crash_prob: float, batch: int, seq: int,
        local_steps: int, lr: float, seed: int = 0, ckpt: str = None,
        full_size: bool = False, log_every: int = 10):
    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    model = build_model(cfg)
    setup = SiloSetup(model, n_clients=n_clients, local_steps=local_steps,
                      learning_rate=lr)
    mesh = mesh_lib.make_local_mesh()

    key = jax.random.PRNGKey(seed)
    global_w = model.init(key)
    state = {
        'global': global_w,
        'local': protocol.broadcast_global(global_w, n_clients),
        'cache': protocol.broadcast_global(global_w, n_clients),
    }

    # synthetic federated token streams, one shard per client
    toks = make_lm_tokens(n_docs=n_clients * batch * 4, seq_len=seq,
                          vocab=cfg.vocab_size, seed=seed)
    env = EnvSpec(m=n_clients, crash_prob=crash_prob,
                  dataset_size=toks.shape[0], batch_size=batch, epochs=1,
                  t_lim=3600.0, seed=seed).build()
    weights = jnp.asarray(env.weights, jnp.float32)

    step = jax.jit(setup.train_step, donate_argnums=(0,))
    versions = np.zeros(n_clients, int)
    committed_prev = np.ones(n_clients, bool)
    picked_prev = np.zeros(n_clients, bool)
    rng = np.random.default_rng(seed)
    history = []

    with mesh:
        for t in range(1, rounds + 1):
            up, dep, _ = protocol.classify_versions(
                jnp.asarray(versions), t - 1, lag_tolerance,
                jnp.asarray(committed_prev))
            up, dep = np.asarray(up), np.asarray(dep)
            sync = up | dep
            crashed, _ = env.draw_round()
            arrival = env.t_dist(int(sync.sum())) + 2 * env.t_updown + \
                env.full_train_time()
            arrival = np.where(~crashed, arrival, np.inf)
            sel = selection.cfcfm(arrival, ~crashed, picked_prev, fraction,
                                  env.t_lim)
            versions[sync] = t - 1
            versions[sel.committed] = t

            doc_idx = rng.integers(0, toks.shape[0],
                                   size=(n_clients, batch))
            tb = toks[doc_idx]
            round_batch = {
                'tokens': jnp.asarray(tb[..., :seq]),
                'labels': jnp.asarray(tb[..., 1:seq + 1]),
                'meta': {
                    'sync': jnp.asarray(sync),
                    'picked': jnp.asarray(sel.picked),
                    'undrafted': jnp.asarray(sel.undrafted),
                    'deprecated': jnp.asarray(dep),
                    'completed': jnp.asarray(sel.committed),
                    'weights': weights,
                },
            }
            if cfg.family == 'vlm':
                round_batch['patch_embeds'] = jnp.zeros(
                    (n_clients, batch, cfg.n_patches, cfg.d_model), jnp.float32)
            if cfg.family == 'audio':
                round_batch['frame_embeds'] = jnp.zeros(
                    (n_clients, batch, cfg.enc_seq, cfg.d_model), jnp.float32)
            state, metrics = step(state, round_batch)
            committed_prev = sel.committed.copy()
            picked_prev = sel.picked.copy()
            history.append(float(metrics['loss']))
            if t % log_every == 0 or t == rounds:
                print(f'round {t:4d} loss {history[-1]:.4f} '
                      f'picked {int(sel.picked.sum())}/{n_clients} '
                      f'crashed {int(crashed.sum())}', flush=True)

    if ckpt:
        checkpoint.save(ckpt, state['global'],
                        {'arch': arch, 'rounds': rounds})
        print('checkpoint saved to', ckpt)
    return history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', choices=ARCH_IDS, default='qwen3-1.7b')
    ap.add_argument('--rounds', type=int, default=30)
    ap.add_argument('--clients', type=int, default=4)
    ap.add_argument('--fraction', type=float, default=0.5)
    ap.add_argument('--lag-tolerance', type=int, default=5)
    ap.add_argument('--crash-prob', type=float, default=0.2)
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--seq', type=int, default=64)
    ap.add_argument('--local-steps', type=int, default=2)
    ap.add_argument('--lr', type=float, default=0.05)
    ap.add_argument('--ckpt', default=None)
    ap.add_argument('--full-size', action='store_true')
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.time()
    hist = run(args.arch, rounds=args.rounds, n_clients=args.clients,
               fraction=args.fraction, lag_tolerance=args.lag_tolerance,
               crash_prob=args.crash_prob, batch=args.batch, seq=args.seq,
               local_steps=args.local_steps, lr=args.lr, ckpt=args.ckpt,
               full_size=args.full_size)
    print(f'done: loss {hist[0]:.3f} -> {hist[-1]:.3f} in {time.time()-t0:.0f}s')


if __name__ == '__main__':
    main()
