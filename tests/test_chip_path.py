"""Rules that keep the accelerator path honest, checked on the CPU.

No fallback hides the device: the chip smoke script refuses to run
without a TPU, interpret mode runs only on a CPU platform, the compile
cache sits where JAX or a fixed checkout path says, a sharded fleet uses
every device, and meshes and in-place row buffers refuse shapes they
cannot lay out.
"""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import backend, ops
from repro.launch import compile_cache, mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('PYTHONPATH', None)     # the script finds src/ itself
    env.update(extra)
    return env


class TestChipSmokeRefusesCpu:
    def test_exits_nonzero_without_tpu(self):
        out = subprocess.run([sys.executable, str(ROOT / 'chip_smoke.py')],
                             env=_cpu_env(), cwd=ROOT, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode != 0
        assert 'no TPU' in out.stderr
        assert '"ok"' not in out.stdout

    def test_exits_nonzero_outside_a_checkout(self, tmp_path):
        shutil.copy(ROOT / 'chip_smoke.py', tmp_path / 'chip_smoke.py')
        out = subprocess.run([sys.executable, 'chip_smoke.py'],
                             env=_cpu_env(), cwd=tmp_path,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout


class TestInterpretOnlyOnCpu:
    def test_interpret_never_on_tpu(self, monkeypatch):
        monkeypatch.setenv('REPRO_FORCE_INTERPRET', '1')
        monkeypatch.setattr(backend, 'kernel_platform', lambda: 'tpu')
        assert backend.interpret() is False

    def test_default_device_decides(self):
        """The platform a computation is placed on is read when a kernel
        is traced: a CPU default device means interpret mode."""
        with jax.default_device(jax.devices('cpu')[0]):
            assert backend.kernel_platform() == 'cpu'
            assert backend.interpret() is True


class TestCompileCache:
    def test_leaves_a_set_directory_alone(self, monkeypatch):
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/somewhere/else')
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == '/somewhere/else'
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.enable_compile_cache()
            assert path == str(ROOT / '.jax_cache')
            assert jax.config.jax_compilation_cache_dir == path
            assert compile_cache.enable_compile_cache() == path
        finally:
            jax.config.update('jax_compilation_cache_dir', before)


class TestShapesThatCannotBeLaidOut:
    def test_local_mesh_refuses_odd_device_counts(self, monkeypatch):
        dev = jax.devices()[0]
        monkeypatch.setattr(jax, 'devices', lambda *a: [dev] * 3)
        with pytest.raises(ValueError, match='3 devices'):
            mesh.make_local_mesh()

    def test_scatter_rows_refuses_partial_row_groups(self):
        buf = jnp.zeros((backend.row_pad(11) - 1, 256), jnp.float32)
        with pytest.raises(ValueError, match='multiple of 8'):
            ops.scatter_rows(buf, jnp.zeros((2,), jnp.int32),
                             jnp.zeros((2, 256), jnp.float32), tile=256)

    def test_row_pad(self):
        assert [backend.row_pad(r) for r in (1, 8, 9, 101)] == \
            [8, 8, 16, 104]


FLEET_PAD_SCRIPT = '''
import jax, numpy as np
from repro import api
from repro.data import make_regression, partition
from repro.data.tasks import regression_task
from repro.fedsim import EnvSpec
assert len(jax.devices()) == 4, jax.devices()
env = EnvSpec(m=5, crash_prob=0.3, dataset_size=506, batch_size=5,
              epochs=3, t_lim=830.0, seed=3)
x, y = make_regression()
task = regression_task(partition(x, y, env.build().partition_sizes, 5,
                                 seed=1), lr=1e-3, epochs=3)
members = lambda: [api.SweepMember(env=env.replace(draw_seed=s),
                                   fraction=0.5, seed=s) for s in range(3)]
def sweep(engine):
    runner = api.Experiment(task, env, api.SafaSpec(), api.ExecSpec(
        engine=engine, eval_every=4), rounds=4).compile()
    return runner, runner.run_sweep(members())
runner, fleet = sweep('fleet')
devices = {sh.device for leaf in jax.tree.leaves(runner.fleet_global)
           for sh in leaf.addressable_shards}
_, seq = sweep('sequential')
for a, b in zip(fleet, seq):
    for la, lb in zip(jax.tree.leaves(a.final_global),
                      jax.tree.leaves(b.final_global)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
print('FLEET', len(fleet), 'ON', len(devices), 'DEVICES')
'''


def test_fleet_not_dividing_device_count_is_padded():
    """3 members on 4 forced host devices: the fleet is padded to 4, runs
    on every device, and each member matches its sequential run."""
    env = _cpu_env(XLA_FLAGS=os.environ.get('XLA_FLAGS', '')
                   + ' --xla_force_host_platform_device_count=4',
                   PYTHONPATH=str(ROOT / 'src'))
    out = subprocess.run([sys.executable, '-c', FLEET_PAD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert 'FLEET 3 ON 4 DEVICES' in out.stdout
