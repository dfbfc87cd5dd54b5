"""Local multi-process launcher: spawn N worker processes over the
Gloo-backed CPU runtime, honoring the reference DMLC_* env contract.

This is the harness under the ``dist`` CI stage, the dist-process
tests, and ``tools/launch.py`` (which delegates here): it turns "run
this command as a 2-host pod" into one call that

  * exports the reference env per worker (``DMLC_ROLE=worker``,
    ``DMLC_PS_ROOT_URI/PORT``, ``DMLC_NUM_WORKER``,
    ``DMLC_WORKER_ID``) so reference training scripts — and
    ``mxnet_tpu._dist_init`` — launch unchanged;
  * pins workers to the CPU platform with
    ``--xla_force_host_platform_device_count`` when ``local_devices``
    is set (the 1-device-per-host pod simulation on one machine);
  * captures each rank's stdout+stderr to its own log file
    (``worker-<rank>.log``) so interleaved output never hides which
    host failed;
  * terminates the surviving workers when one fails or the deadline
    passes — a dead coordinator would otherwise leave its peers
    blocked in ``jax.distributed.initialize`` until the init timeout;
  * propagates resumability: rc 75 (``EX_TEMPFAIL``, the preemption
    contract of docs/RESILIENCE.md) from any worker makes
    :func:`exit_code` 75, so an outer scheduler restarts the job,
    while any other non-zero rc propagates as the hard failure it is.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

__all__ = ['WorkerResult', 'LaunchResult', 'LaunchError', 'launch_local',
           'free_port', 'worker_env']

_RESUMABLE_RC = 75          # mirrors MXNET_TPU_PREEMPT_EXIT_CODE default


def free_port():
    s = socket.socket()
    s.bind(('', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _resumable_rc():
    try:
        return int(os.environ.get('MXNET_TPU_PREEMPT_EXIT_CODE',
                                  _RESUMABLE_RC))
    except ValueError:
        return _RESUMABLE_RC


class LaunchError(RuntimeError):
    """The requested pod cannot be started on this host as asked."""


class WorkerResult:
    """One rank's outcome: ``rank``, ``returncode``, ``log_path``."""

    __slots__ = ('rank', 'returncode', 'log_path')

    def __init__(self, rank, returncode, log_path):
        self.rank = rank
        self.returncode = returncode
        self.log_path = log_path

    @property
    def resumable(self):
        return self.returncode == _resumable_rc()

    def log_tail(self, max_bytes=4096):
        if not self.log_path or not os.path.exists(self.log_path):
            return ''
        with open(self.log_path, 'rb') as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - max_bytes))
            return f.read().decode('utf-8', 'replace')

    def __repr__(self):
        return 'WorkerResult(rank=%d, rc=%r, log=%r)' % (
            self.rank, self.returncode, self.log_path)


class LaunchResult(list):
    """List of :class:`WorkerResult` plus pod-level verdicts."""

    @property
    def returncodes(self):
        return [w.returncode for w in self]

    @property
    def ok(self):
        return all(w.returncode == 0 for w in self)

    def exit_code(self):
        """Pod rc with resumable propagation: 0 when every worker
        exited clean; the resumable rc (75) when at least one worker
        was preempted and NO worker failed hard; otherwise the first
        hard failure's rc. Workers the launcher itself terminated
        (SIGTERM, rc -15) after a peer failed are collateral, not the
        cause — the peer's rc wins when one exists."""
        rc75 = _resumable_rc()
        hard = [w.returncode for w in self
                if w.returncode not in (0, rc75)]
        if hard:
            causes = [rc for rc in hard if rc != -15]
            return causes[0] if causes else hard[0]
        if any(w.returncode == rc75 for w in self):
            return rc75
        return 0

    def failures(self):
        return [w for w in self if w.returncode != 0]


def worker_env(rank, num_workers, port, uri='127.0.0.1', env=None,
               local_devices=None, platform=None):
    """The per-worker environment (the DMLC_* reference contract plus
    the CPU-rig pinning) — exposed so cluster schedulers exporting the
    variables themselves stay byte-compatible with the local spawner."""
    wenv = dict(os.environ, **(env or {}))
    wenv.update({
        'DMLC_ROLE': 'worker',
        'DMLC_PS_ROOT_URI': uri,
        'DMLC_PS_ROOT_PORT': str(port),
        'DMLC_NUM_WORKER': str(num_workers),
        'DMLC_NUM_SERVER': '0',
        'DMLC_WORKER_ID': str(rank),
    })
    if platform:
        wenv['JAX_PLATFORMS'] = platform
    if local_devices:
        flags = wenv.get('XLA_FLAGS', '')
        # strip a pre-existing forced count (the parent test env forces
        # 8; a spawned 1-device-per-host worker must not inherit it)
        parts = [p for p in flags.split()
                 if not p.startswith(
                     '--xla_force_host_platform_device_count')]
        parts.append('--xla_force_host_platform_device_count=%d'
                     % int(local_devices))
        wenv['XLA_FLAGS'] = ' '.join(parts)
    return wenv


def launch_local(num_workers, command, env=None, coordinator_port=None,
                 timeout=None, log_dir=None, local_devices=None,
                 platform=None, poll_s=0.2):
    """Spawn ``num_workers`` local processes running ``command`` with
    the DMLC_* worker env set; returns a :class:`LaunchResult`.

    ``log_dir`` (strongly recommended; required for post-mortems)
    captures each rank's stdout+stderr into ``worker-<rank>.log``.
    ``local_devices`` forces that many virtual CPU devices per worker;
    ``platform`` pins ``JAX_PLATFORMS`` (pass 'cpu' for the Gloo rig).
    This launcher IS the Gloo/CPU rig: it assigns no accelerator to a
    worker, and an accelerator belongs to one process, so several
    workers whose ``JAX_PLATFORMS`` is not ``cpu`` would each claim
    every chip of the host and fail or hang — that combination raises
    :class:`LaunchError` before anything is spawned.
    If any worker fails hard (or ``timeout`` seconds elapse), the
    remaining workers are terminated. A worker exiting with the
    resumable rc (75) also ends the pod — a preempted host means the
    job checkpoint-resumes — but :meth:`LaunchResult.exit_code`
    reports 75, not a hard failure.
    """
    port = coordinator_port or free_port()
    effective = worker_env(0, num_workers, port, env=env,
                           platform=platform).get('JAX_PLATFORMS')
    if num_workers > 1 and effective != 'cpu':
        raise LaunchError(
            'launch_local(%d workers) with JAX_PLATFORMS=%r: the local '
            'launcher is the Gloo/CPU rig and gives no worker a chip of '
            'its own, so every worker would claim every accelerator of '
            'this host. Pass platform=\'cpu\' (or export '
            'JAX_PLATFORMS=cpu); multi-process launch on TPU is not '
            'brought up (docs/DISTRIBUTED.md).'
            % (num_workers, effective))
    rc75 = _resumable_rc()
    if local_devices is None:
        # knob default (docs/DISTRIBUTED.md): 0 leaves XLA_FLAGS alone
        try:
            from .. import config as _config
            local_devices = int(
                _config.get('MXNET_TPU_DIST_LOCAL_DEVICES') or 0) \
                or None
        except Exception:
            local_devices = None
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    procs = []
    logs = []
    files = []
    try:
        try:
            for wid in range(num_workers):
                wenv = worker_env(wid, num_workers, port, env=env,
                                  local_devices=local_devices,
                                  platform=platform)
                if log_dir:
                    log_path = os.path.join(log_dir,
                                            'worker-%d.log' % wid)
                    lf = open(log_path, 'wb')
                    files.append(lf)
                    stdout, stderr = lf, subprocess.STDOUT
                else:
                    log_path, stdout, stderr = None, None, None
                logs.append(log_path)
                procs.append(subprocess.Popen(command, env=wenv,
                                              stdout=stdout,
                                              stderr=stderr))
        except BaseException:
            # a failed spawn (bad command path, EAGAIN) must not leak
            # the ranks already started — they would otherwise block
            # in the join handshake until the init timeout
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
            raise

        deadline = time.time() + timeout if timeout else None
        failed = False
        while True:
            states = [p.poll() for p in procs]
            if all(s is not None for s in states):
                break
            if any(s not in (None, 0) for s in states) or \
                    (deadline and time.time() > deadline):
                failed = True
                break
            time.sleep(poll_s)
        if failed:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
    finally:
        for lf in files:
            try:
                lf.close()
            except OSError:
                pass
    out = LaunchResult()
    for wid, (p, log_path) in enumerate(zip(procs, logs)):
        rc = p.returncode if p.returncode is not None else -15
        out.append(WorkerResult(wid, rc, log_path))
    _record_launch(out, num_workers, rc75)
    return out


def _record_launch(result, num_workers, rc75):
    try:
        from .. import observability as _obs
        if _obs.enabled():
            _obs.record_event(
                'dist_launch', workers=num_workers,
                returncodes=result.returncodes,
                resumable=result.exit_code() == rc75)
    except Exception:
        pass
