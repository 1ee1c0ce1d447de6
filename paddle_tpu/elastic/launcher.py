"""The ReplicaLauncher seam: how the autoscaler turns a scale decision
into an actual replica process (and back).

The :class:`~paddle_tpu.elastic.autoscaler.Autoscaler` never spawns or
kills anything itself — it calls ``launcher.launch() -> url`` and
``launcher.retire(url)`` through this seam, so the same control loop
drives real subprocesses (:class:`ProcessReplicaLauncher` →
``python -m paddle_tpu.serving.tier.replica``), in-process stacks in
tests/bench (:class:`CallableReplicaLauncher`), or a cluster scheduler
(implement the two methods).
"""
from __future__ import annotations

import collections
import json
import logging
import os
import subprocess
import sys
import threading
import time

from ..log_helper import get_logger

__all__ = ['ReplicaLauncher', 'ProcessReplicaLauncher',
           'CallableReplicaLauncher']

_logger = get_logger(
    __name__, logging.INFO,
    fmt='%(asctime)s-%(levelname)s: [elastic] %(message)s')


class ReplicaLauncher:
    """Abstract seam. ``launch()`` returns the new replica's base URL
    (the replica may still be COLD — the router's warmup gate, not the
    launcher, decides routability); ``retire(url)`` tears one down. The
    autoscaler only calls ``retire`` after the router drained the replica
    to zero in-flight work."""

    def launch(self):
        raise NotImplementedError

    def retire(self, url):
        raise NotImplementedError

    def close(self):
        """Tear down everything this launcher started (best effort)."""


class ProcessReplicaLauncher(ReplicaLauncher):
    """Spawns real decode-replica subprocesses
    (``python -m paddle_tpu.serving.tier.replica --port 0``) and parses
    the ready-line handshake for the bound port. ``lazy_warmup=True``
    (the default) returns as soon as the process is serving — COLD — so
    scale-up latency is the spawn, not the compile cliff; the router's
    warmup gate holds traffic until ``/healthz`` flips ``warmup.done``."""

    def __init__(self, seed=None, extra_args=None, env=None,
                 lazy_warmup=True, ready_timeout_s=120.0):
        self.seed = seed
        self.extra_args = list(extra_args or [])
        self.env = dict(env) if env is not None else None
        self.lazy_warmup = bool(lazy_warmup)
        self.ready_timeout_s = float(ready_timeout_s)
        self._procs = {}            # url -> subprocess.Popen

    def launch(self):
        cmd = [sys.executable, '-m', 'paddle_tpu.serving.tier.replica',
               '--port', '0']
        if self.seed is not None:
            cmd += ['--seed', str(int(self.seed))]
        if self.lazy_warmup:
            cmd.append('--lazy-warmup')
        cmd += self.extra_args
        env = dict(os.environ if self.env is None else self.env)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env,
                                text=True)
        # keep the last lines of the child's stderr: when the launch fails
        # (on a one-chip machine a second replica cannot get the chip) the
        # reason is in them. The thread drains the pipe for the replica's
        # whole life, so a chatty child never blocks on a full pipe.
        stderr_tail = collections.deque(maxlen=40)
        drain = threading.Thread(target=stderr_tail.extend,
                                 args=(proc.stderr,), daemon=True,
                                 name='paddle-tpu-replica-stderr')
        drain.start()
        deadline = time.monotonic() + self.ready_timeout_s
        line = ''
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.strip() or proc.poll() is not None:
                break
        try:
            ready = json.loads(line)
            assert ready.get('ready') and 'port' in ready
        except (ValueError, AssertionError):
            proc.kill()
            proc.wait(timeout=10)
            drain.join(timeout=10)
            raise RuntimeError(
                f'replica launch failed: no ready line within '
                f'{self.ready_timeout_s:.0f}s (got {line!r}, '
                f'rc={proc.poll()}); child stderr tail:\n'
                + ''.join(stderr_tail)[-2000:])
        url = f"http://127.0.0.1:{ready['port']}"
        self._procs[url] = proc
        _logger.info('launched replica %s (pid %d)', url, proc.pid)
        return url

    def retire(self, url):
        proc = self._procs.pop(url.rstrip('/'), None)
        if proc is None:
            raise KeyError(f'unknown replica {url}')
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
        _logger.info('retired replica %s', url)

    def close(self):
        for url in list(self._procs):
            try:
                self.retire(url)
            except Exception:
                pass


class CallableReplicaLauncher(ReplicaLauncher):
    """Launcher over two callables — ``launch_fn() -> url`` and
    ``retire_fn(url)`` — for in-process replica stacks (tests, the
    autoscaler bench) and custom schedulers."""

    def __init__(self, launch_fn, retire_fn, close_fn=None):
        self._launch = launch_fn
        self._retire = retire_fn
        self._close = close_fn
        self.launched = []
        self.retired = []

    def launch(self):
        url = self._launch()
        self.launched.append(url)
        return url

    def retire(self, url):
        self._retire(url)
        self.retired.append(url)

    def close(self):
        if self._close is not None:
            self._close()
