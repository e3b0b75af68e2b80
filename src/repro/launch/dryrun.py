"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, SPMD-partitions and compiles on the production meshes,
and extract roofline terms from the compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs import (ARCH_IDS, INPUT_SHAPES, get_config,
                           shape_supported)
from repro.launch import analytic, hlo_parse
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rf
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import ServeSetup, SiloSetup
from repro.models.model import build_model

#: The chip whose roofline the dry run reports (a key of ``rf.PEAKS``).
TARGET_DEVICE_KIND = 'TPU v5 lite'


def active_params(cfg, model) -> int:
    """Per-token active parameters (MoE: shared + top-1 expert)."""
    n = model.n_params()
    if cfg.n_experts:
        expert = 3 * cfg.d_model * cfg.d_ff
        n_moe_layers = cfg.n_layers // cfg.moe_every
        n -= (cfg.n_experts - 1) * expert * n_moe_layers
    return n


def lower_one(arch_id: str, shape_name: str, *, multi_pod: bool,
              fedavg_baseline: bool = False, extra_cfg=None,
              profile: str = 'tp'):
    """Returns a result dict with memory/cost/roofline info."""
    cfg = get_config(arch_id)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    model = build_model(cfg)
    t0 = time.time()

    from repro import sharding as shd
    from repro.launch.steps import SERVE_PROFILES
    if shape.kind != 'train' and profile in SERVE_PROFILES:
        serve_rules = SERVE_PROFILES[profile]
    else:
        serve_rules = None
    rules = shd.PROFILES.get(profile, shd.DEFAULT_RULES)
    if profile == 'fsdp' and multi_pod:
        rules = shd.FSDP_MULTIPOD_RULES
    if shape.kind == 'train':
        n_cl_axes = rules.get('clients', ('pod', 'data'))
        setup = SiloSetup(model,
                          n_clients=mesh_lib.n_clients(mesh, n_cl_axes),
                          rules=rules)
        state_sds = setup.state_sds()
        batch_sds = setup.client_batch(shape)
        state_sh, batch_sh = setup.shardings(mesh, shape)
        step = setup.fedavg_train_step if fedavg_baseline else setup.train_step
        with mesh:
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              donate_argnums=(0,)).lower(state_sds, batch_sds)
        tokens = shape.global_batch * shape.seq_len
        mf = rf.model_flops_estimate(active_params(cfg, model), tokens,
                                     'train')
    elif shape.kind == 'prefill':
        setup = ServeSetup(model, serve_rules=serve_rules)
        p_sh = setup.param_shardings(mesh)
        b_sh = setup.prefill_shardings(mesh, shape)
        with mesh:
            lowered = jax.jit(setup.prefill_step,
                              in_shardings=(p_sh, b_sh)).lower(
                model.param_shapes(), setup.prefill_batch(shape))
        tokens = shape.global_batch * shape.seq_len
        mf = rf.model_flops_estimate(active_params(cfg, model), tokens,
                                     'prefill')
    else:  # decode
        setup = ServeSetup(model, serve_rules=serve_rules)
        p_sh = setup.param_shardings(mesh)
        cache_sds, tok_sds = setup.decode_batch(shape)
        cache_sh, tok_sh = setup.decode_shardings(mesh, shape)
        with mesh:
            lowered = jax.jit(setup.serve_step,
                              in_shardings=(p_sh, cache_sh, tok_sh),
                              donate_argnums=(1,)).lower(
                model.param_shapes(), cache_sds, tok_sds)
        tokens = shape.global_batch  # one token per sequence
        mf = rf.model_flops_estimate(active_params(cfg, model), tokens,
                                     'decode')

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = hlo_parse.analyze_collectives(hlo)

    # analytic compute/memory terms (XLA CPU cost analysis counts loop
    # bodies once — see EXPERIMENTS.md §Dry-run); collective term from
    # trip-count-corrected HLO parsing.
    n_cl = mesh_lib.n_clients(mesh) if shape.kind == 'train' else 1
    flops = analytic.flops_estimate(
        cfg, kind=shape.kind, batch=shape.global_batch, seq=shape.seq_len,
        n_active=active_params(cfg, model))
    byts = analytic.bytes_estimate(
        cfg, kind=shape.kind, batch=shape.global_batch, seq=shape.seq_len,
        n_params=model.n_params(), n_clients=n_cl)
    roof = rf.Roofline(flops=flops, hbm_bytes=byts,
                       coll_bytes=float(coll['adjusted_total_bytes']),
                       chips=chips, device_kind=TARGET_DEVICE_KIND,
                       model_flops=mf)

    cost = compiled.cost_analysis()
    result = {
        'arch': arch_id, 'shape': shape_name,
        'mesh': mesh_lib.describe(mesh), 'chips': chips,
        'kind': shape.kind, 'profile': profile,
        'step': 'fedavg' if fedavg_baseline else
                ('safa' if shape.kind == 'train' else 'serve'),
        'lower_s': round(t_lower, 1), 'compile_s': round(t_compile, 1),
        'arg_bytes': getattr(mem, 'argument_size_in_bytes', 0),
        'temp_bytes': getattr(mem, 'temp_size_in_bytes', 0),
        'peak_bytes': getattr(mem, 'peak_memory_in_bytes', 0),
        **roof.as_dict(),
        'collectives': coll['counts'],
        'collective_bytes_by_kind': coll['bytes'],
        'coll_bytes_raw': float(coll['total_bytes']),
        'xla_flops_body_once': float(cost.get('flops', 0.0)),
        'xla_bytes_body_once': float(cost.get('bytes accessed', 0.0)),
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', choices=ARCH_IDS)
    ap.add_argument('--shape', choices=list(INPUT_SHAPES))
    ap.add_argument('--all', action='store_true')
    ap.add_argument('--multi-pod', action='store_true')
    ap.add_argument('--fedavg-baseline', action='store_true')
    ap.add_argument('--profile', choices=('tp', 'fsdp', 'splitkv'),
                    default='tp')
    ap.add_argument('--out', default=None)
    ap.add_argument('--skip-existing', action='store_true')
    args = ap.parse_args(argv)
    enable_compile_cache()

    combos = []
    if args.all:
        # Granite trains through the federated LoRA task
        # (repro.data.lm_task), not the production-mesh steps lowered here
        for a in (a for a in ARCH_IDS if get_config(a).family != 'granite'):
            for s in INPUT_SHAPES:
                if shape_supported(a, s):
                    combos.append((a, s))
    else:
        assert args.arch and args.shape, '--arch/--shape or --all'
        combos = [(args.arch, args.shape)]

    done = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r['arch'], r['shape'], r['mesh'], r['step'], r.get('profile', 'tp')))
                except Exception:
                    pass

    mesh_desc = mesh_lib.describe(mesh_lib.make_production_mesh(
        multi_pod=args.multi_pod))
    failures = []
    for arch, shape in combos:
        kind = INPUT_SHAPES[shape].kind
        step_name = ('fedavg' if args.fedavg_baseline else
                     ('safa' if kind == 'train' else 'serve'))
        if (arch, shape, mesh_desc, step_name, args.profile) in done:
            continue
        try:
            res = lower_one(arch, shape, multi_pod=args.multi_pod,
                            fedavg_baseline=args.fedavg_baseline,
                            profile=args.profile)
            line = json.dumps(res)
            print(line, flush=True)
            if args.out:
                with open(args.out, 'a') as f:
                    f.write(line + '\n')
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            print(f'FAIL {arch} {shape}: {e!r}', file=sys.stderr, flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
