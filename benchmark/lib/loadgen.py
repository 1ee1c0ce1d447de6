"""The one traffic generator for serving cells, and the client process that
plays it.

A traffic mix is a data file (benchmark/traffic/<name>.json, key "load");
everything here is driven by its parameters, so a new mix needs no code:

  loop          "closed": `clients` callers, each sending its next request
                when the previous stream ends (a replica behind a router's
                in-flight limit, an offline job). It is the only loop here:
                a cell that needs arrivals on a schedule brings its
                generator as a file of its own and proves it on the chip.
  clients       the callers.
  prompt_len /  {"median", "sigma", "min", "max"}: log-normal, clipped. Real
  output_len    lengths are heavy-tailed; a fixed length hides padding and
                batching effects. Lengths are dealt in ROUNDS of `clients`
                requests: a round holds each of `clients` evenly spaced
                quantiles of the distribution exactly once, and the run's
                seed shuffles which client takes which (request k of client
                c takes the c-th of the seed's deal of round k). Every seed
                so plays the same multiset of lengths per round, at other
                places and in another order: drawn independently, the work
                of a 45 s window of ~145 requests differed from seed to
                seed and tokens/s ranged over 7% in three runs; dealt, over
                6% in four (my chip runs, PR 22).
  vocab         token ids are drawn from [1, vocab).

Lengths, their places and the token ids (and, in the runner, the weights)
all come from the seed: the same seed gives the same requests in the same
order for each client, another seed gives others.

This module never imports jax: the client process must not touch the chip,
and its threads must not take the interpreter lock from the server's
dispatch loop. It is started as `python loadgen.py <spec.json>`; it prints
`OPEN <perf_counter>` when the ramp is over and the window opens, measures
for spec["seconds"], writes spec["results"], and prints `DONE`.
time.perf_counter is CLOCK_MONOTONIC on Linux, one clock for both processes.
"""
from __future__ import annotations

import http.client
import json
import math
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

_NORMAL = statistics.NormalDist()
_WHAT = {'prompt': 1, 'output': 2, 'ramp': 3}


# -- drawing requests ---------------------------------------------------------

def quantile_len(spec, u):
    """The length at quantile u (0..1) of the clipped log-normal."""
    x = math.exp(math.log(spec['median'])
                 + spec['sigma'] * _NORMAL.inv_cdf(min(max(u, 1e-9), 1 - 1e-9)))
    return int(min(max(round(x), spec['min']), spec['max']))


def quantile_of(load, seed, what, round_k, position):
    """The quantile request `position` of round `round_k` takes for `what`
    ('prompt', 'output', 'ramp'): the seed's shuffle of the mid-points of
    `clients` equal strata."""
    n = load['clients']
    order = np.random.default_rng([seed, _WHAT[what], round_k]).permutation(n)
    return (order[position] + 0.5) / n


def draw_request(rng, load, seed, round_k, position):
    """(prompt ids, max_new_tokens) of one request: its lengths from its
    place in the seed's deal of its round, its tokens from the client's own
    seeded generator."""
    plen = quantile_len(load['prompt_len'],
                        quantile_of(load, seed, 'prompt', round_k, position))
    new = quantile_len(load['output_len'],
                       quantile_of(load, seed, 'output', round_k, position))
    return rng.integers(1, load['vocab'], plen).tolist(), new


# -- one request over HTTP ----------------------------------------------------

class _Client:
    """One keep-alive connection to POST /generate, streaming NDJSON."""

    def __init__(self, port, timeout):
        self.port, self.timeout = port, timeout
        self.conn = None

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def generate(self, prompt, max_new, rec, headers, stop, first=None):
        """Play one request into `rec`: sent, token receive times, outcome.
        `stop` set means the window closed: the stream is abandoned.
        `first`, if given, is called when the first token arrives."""
        body = json.dumps({'prompt': prompt, 'max_new_tokens': max_new,
                           'stream': True})
        if self.conn is None:
            self.conn = http.client.HTTPConnection('127.0.0.1', self.port,
                                                   timeout=self.timeout)
        rec['sent'] = time.perf_counter()
        try:
            self.conn.request('POST', '/generate', body,
                              {'Content-Type': 'application/json',
                               **headers})
            resp = self.conn.getresponse()
            if resp.status != 200:
                rec['error'] = f'HTTP {resp.status}: {resp.read()[:200]!r}'
                return
            streamed = []
            while True:
                line = resp.readline()
                now = time.perf_counter()
                if stop.is_set():
                    rec['abandoned'] = True
                    self.close()
                    return
                if not line:
                    rec['error'] = 'stream ended without a done line'
                    self.close()
                    return
                msg = json.loads(line)
                if 'token' in msg:
                    streamed.append(msg['token'])
                    rec['t'].append(now)
                    if first is not None and len(streamed) == 1:
                        first()
                elif msg.get('done'):
                    resp.read()             # the terminating chunk
                    if msg['tokens'] != streamed or len(streamed) != max_new:
                        rec['error'] = (f'{len(streamed)} tokens streamed, '
                                        f"{len(msg['tokens'])} in the done "
                                        f'line, {max_new} asked')
                    rec['done'] = now
                    return
                else:
                    rec['error'] = f"{msg.get('error')}: {msg.get('message')}"
                    self.close()
                    return
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec['error'] = f'{type(e).__name__}: {e}'
            self.close()


def _trace_headers(rng, traced):
    if not traced:
        return {}
    ids = ''.join(f'{int(x):02x}' for x in rng.integers(0, 256, 16))
    return {'X-PaddleTPU-Trace': f'{ids[:16]}-{ids[16:]}-1'}


# -- the client process -------------------------------------------------------

def _closed_loop(spec, records, lock, stop):
    """Starts the clients; returns their threads when the ramp is over."""
    load, seed = spec['load'], spec['seed']
    first_token = [threading.Event() for _ in range(load['clients'])]

    def client(c):
        rng = np.random.default_rng([seed, c])
        http_client = _Client(spec['port'], spec['request_timeout'])
        k = 0
        while not stop.is_set():
            prompt, max_new = draw_request(rng, load, seed, k, c)
            if k == 0:
                # ramp: cut to an evenly spread fraction, so that the slots
                # do not all finish together when the window opens
                part = quantile_of(load, seed, 'ramp', 0, c)
                max_new = max(min(load['output_len']['min'], max_new),
                              math.ceil(max_new * part))
            rec = {'client': c, 'k': k, 'prompt_len': len(prompt),
                   'asked': max_new, 'due': time.perf_counter(), 't': []}
            with lock:
                records.append(rec)
            # the window opens when every client has its first token; a
            # first request that fails must not hold it shut
            http_client.generate(prompt, max_new, rec,
                                 _trace_headers(rng, spec['traced']), stop,
                                 first=first_token[c].set if k == 0 else None)
            first_token[c].set()
            if 'error' in rec:
                stop.wait(0.05)       # do not hammer a server that refuses
            k += 1
        http_client.close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(load['clients'])]
    for t in threads:
        t.start()
    for ev in first_token:
        ev.wait()
    return threads


def main(spec_path):
    with open(spec_path) as f:
        spec = json.load(f)
    if spec['load']['loop'] != 'closed':
        sys.exit(f"loadgen: no loop {spec['load']['loop']!r} here, only "
                 "'closed'")
    records, lock, stop = [], threading.Lock(), threading.Event()
    threads = _closed_loop(spec, records, lock, stop)
    opened = time.perf_counter()
    print(f'OPEN {opened!r}', flush=True)
    close_at = opened + spec['seconds']
    time.sleep(max(0.0, close_at - time.perf_counter()))
    stop.set()
    for t in threads:
        t.join(2.0)
    with lock:
        snapshot = [dict(r, t=list(r['t'])) for r in records]
    with open(spec['results'], 'w') as f:
        json.dump({'open': opened, 'close': close_at,
                   'records': snapshot}, f)
    print('DONE', flush=True)


# -- the parent's side --------------------------------------------------------

class Load:
    """The client process, as seen from the process that holds the chip."""

    def __init__(self, spec, spec_path):
        with open(spec_path, 'w') as f:
            json.dump(spec, f)
        self.spec = spec
        self.proc = subprocess.Popen(
            [sys.executable, __file__, spec_path], stdout=subprocess.PIPE,
            text=True)

    def wait_open(self):
        """Blocks until the ramp is over; the window's opening time."""
        line = self.proc.stdout.readline()
        if not line.startswith('OPEN '):
            raise RuntimeError(f'load generator said {line!r} (exit code '
                               f'{self.proc.poll()})')
        return float(line.split()[1])

    def finish(self, timeout):
        """Waits for the window to close; the client's records."""
        try:
            out, _ = self.proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop()
            raise
        if self.proc.returncode != 0 or 'DONE' not in out:
            raise RuntimeError(f'load generator exited {self.proc.returncode}'
                               f' saying {out[-500:]!r}')
        with open(self.spec['results']) as f:
            return json.load(f)

    def stop(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def reduce(results):
    """Client-side metrics of one window, from the client's records:
    tokens received in the window, time to first token of the requests sent
    in it (from the due time; +inf for a failed one), gaps between the tokens
    of one request, both ends in the window, and how late each request was
    sent."""
    lo, hi = results['open'], results['close']
    tokens, ttft, itl, lag, censored = 0, [], [], [], 0
    attempted = failed = completed = 0
    for r in results['records']:
        ts = r['t']
        tokens += sum(lo <= t < hi for t in ts)
        itl += [b - a for a, b in zip(ts, ts[1:]) if a >= lo and b < hi]
        if r.get('done') is not None and lo <= r['done'] < hi:
            completed += 1
        if not (lo <= r['due'] < hi) or 'sent' not in r:
            continue
        attempted += 1
        lag.append(r['sent'] - r['due'])
        if 'error' in r:
            failed += 1
            ttft.append(math.inf)
        elif ts:
            ttft.append(ts[0] - r['due'])
        else:
            censored += 1       # the window closed before its first token
    return {'window_s': hi - lo, 'tokens': tokens, 'ttft_s': ttft,
            'itl_s': itl, 'send_lag_s': lag, 'attempted': attempted,
            'failed': failed, 'completed': completed, 'censored': censored,
            'errors': sorted({r['error'] for r in results['records']
                              if 'error' in r})[:5]}


if __name__ == '__main__':
    main(sys.argv[1])
