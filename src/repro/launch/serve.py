"""Batched serving driver: prefill + decode with KV/SSM caches.

Serves the (aggregated) global model — e.g. a checkpoint produced by
``repro.launch.train``.  On the production mesh the same ``serve_step``
lowers for the decode_32k / long_500k dry-run shapes.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model


def run(arch: str, *, batch: int, prompt_len: int, gen: int,
        full_size: bool = False, ckpt: str = None, seed: int = 0):
    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    if ckpt:
        params, meta = checkpoint.restore(ckpt, model.param_shapes())
        print('restored checkpoint', meta)
    else:
        params = model.init(key)

    mesh = mesh_lib.make_local_mesh()
    max_len = prompt_len + gen
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)

    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    with mesh:
        cache = model.init_cache(batch, max_len)
        t0 = time.time()
        # prefill token-by-token (reduced-size models; bulk prefill uses
        # forward_logits on real hardware)
        cache, logits = model.prefill(params, cache, prompts)
        t_prefill = time.time() - t0

        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out = [tok]
        t0 = time.time()
        for _ in range(gen - 1):
            cache, logits = decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1)[:, None]
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    toks = jnp.concatenate(out, axis=1)
    print(f'prefill: {batch}x{prompt_len} tokens in {t_prefill:.2f}s')
    print(f'decode:  {batch}x{gen} tokens in {t_decode:.2f}s '
          f'({batch * gen / max(t_decode, 1e-9):.1f} tok/s)')
    print('sample continuation ids:', np.asarray(toks[0, :12]))
    return toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', choices=ARCH_IDS, default='mamba2-130m')
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--prompt-len', type=int, default=32)
    ap.add_argument('--gen', type=int, default=16)
    ap.add_argument('--ckpt', default=None)
    ap.add_argument('--full-size', action='store_true')
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(args.arch, batch=args.batch, prompt_len=args.prompt_len,
        gen=args.gen, ckpt=args.ckpt, full_size=args.full_size)


if __name__ == '__main__':
    main()
