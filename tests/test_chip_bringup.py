"""Bring-up contracts (PR 21): nothing on the main path may pass
without the chip, and nothing may take the chip from the process that
holds it. ``chip_smoke.py`` itself is rehearsed here at toy sizes on
the CPU backend; the real run is ``chiprun -- python chip_smoke.py``.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.data import DataLoader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')


def _run_smoke(args, cwd=REPO, script=SMOKE, devices=2):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=%d'
               % devices)
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def _json_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{')]


# -- chip_smoke.py ------------------------------------------------------------

def test_chip_smoke_refuses_a_cpu_backend():
    """No accelerator, no rehearsal flag: non-zero exit and no result
    line — a CPU run can never be mistaken for a chip run."""
    r = _run_smoke([])
    assert r.returncode != 0
    assert not _json_lines(r.stdout)
    assert 'not "tpu"' in r.stderr


def test_chip_smoke_rehearsal_flag_is_refused_off_the_cpu(
        monkeypatch, capsys):
    """The toy sizes can never end in ``ok: true`` on a chip."""
    import importlib.util
    import jax
    spec = importlib.util.spec_from_file_location('chip_smoke', SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with pytest.raises(SystemExit) as exc:
        smoke.preamble(True)
    assert exc.value.code == 2
    assert 'CPU backend only' in capsys.readouterr().err


def test_chip_smoke_fails_without_the_program(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the
    repo, it fails: the script proves the program, not itself."""
    alone = shutil.copy(SMOKE, str(tmp_path / 'chip_smoke.py'))
    r = _run_smoke(['--rehearse-cpu'], cwd=str(tmp_path), script=alone)
    assert r.returncode != 0
    assert not _json_lines(r.stdout)


def test_chip_smoke_rehearsal_passes_both_legs():
    """Both legs at rehearsal size on two virtual CPU devices (so the
    dp > 1 checks run too). The last stdout line is the result object
    with exactly the keys the driver parses; the line before it is the
    summary, which ends with ``"claim": null``."""
    r = _run_smoke(['--rehearse-cpu'])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        'ok': True,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 2}}
    assert _json_lines(r.stdout) == [lines[-1]]
    tag = '[chip_smoke] summary '
    assert lines[-2].startswith(tag)
    doc = json.loads(lines[-2][len(tag):])
    assert doc['rehearsal'] is True
    assert list(doc)[-1] == 'claim' and doc['claim'] is None
    assert doc['trainer']['dp'] == 2
    assert doc['trainer']['all_reduce_in_step'] is True
    assert len(doc['trainer']['losses']) >= 5
    assert doc['server']['requests'] >= 4
    assert doc['versions']['compile_cache_dir'] == os.environ.get(
        'JAX_COMPILATION_CACHE_DIR', os.path.join(REPO, '.jax_cache'))
    for check in ('trainer leg', 'server leg', 'fallback tokens',
                  'zero retraces', 'within'):
        assert check in r.stdout


# -- one process for each chip -------------------------------------------------

class _PlatformProbe:
    """Each item reports what its worker process can see."""

    def __len__(self):
        return 4

    def __getitem__(self, i):
        import jax
        cpu_only = os.environ.get('JAX_PLATFORMS') == 'cpu' and all(
            d.platform == 'cpu' for d in jax.devices())
        return np.array([float(cpu_only), float(os.getpid())], 'float32')


def test_dataloader_spawn_worker_sees_the_cpu_platform_only(monkeypatch):
    """The parent may hold an accelerator (JAX_PLATFORMS anything but
    cpu); a spawned worker must never open it."""
    monkeypatch.setenv('JAX_PLATFORMS', '')
    loader = DataLoader(_PlatformProbe(), batch_size=2, num_workers=1)
    rows = np.concatenate([b.asnumpy() for b in loader])
    assert rows[:, 0].tolist() == [1.0] * 4
    assert set(rows[:, 1]) != {float(os.getpid())}     # really a child
    assert os.environ['JAX_PLATFORMS'] == ''           # parent restored


def test_local_launcher_refuses_workers_that_would_share_the_chips(
        monkeypatch):
    from mxnet_tpu.dist import launcher
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    cmd = [sys.executable, '-c', 'pass']
    with pytest.raises(launcher.LaunchError, match='Gloo/CPU rig'):
        launcher.launch_local(2, cmd)
    with pytest.raises(launcher.LaunchError):
        launcher.launch_local(2, cmd, env={'JAX_PLATFORMS': 'tpu'})
    # one worker owns the whole host; an explicit cpu pin is the rig
    assert launcher.launch_local(1, cmd).ok
    assert launcher.launch_local(2, cmd, platform='cpu').ok


# -- no fallback that hides the device -----------------------------------------

def test_waitall_lets_device_errors_out(monkeypatch):
    import jax

    def broken():
        raise RuntimeError('INTERNAL: Core halted unexpectedly')

    monkeypatch.setattr(jax, 'effects_barrier', broken)
    with pytest.raises(RuntimeError, match='Core halted'):
        mx.nd.waitall()


def test_interpret_mode_follows_where_the_computation_is_placed(
        monkeypatch):
    """On a tpu backend a kernel is never interpreted — except in the
    serving CPU replay, which traces under jax.default_device(cpu)."""
    import jax
    from mxnet_tpu.ops import pallas
    assert pallas.interpret_mode() is True             # the CPU rig
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert pallas.interpret_mode() is False
    with jax.default_device(jax.devices('cpu')[0]):
        assert pallas.interpret_mode() is True
