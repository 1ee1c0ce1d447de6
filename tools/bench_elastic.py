"""Elastic runtime benchmark (ISSUE 19): autoscaler ramp + resize
accounting. One JSON line per section.

1. ``elastic_autoscale_ramp`` — open-loop Poisson arrivals ramped
   low → high → zero against a 1-replica tier with the REAL autoscaler
   control loop running (real windowed-series signals, real cold-replica
   launches behind the warmup gate, real drain-then-retire on the way
   down). Reports replica-count-over-time, every decision with its
   trigger, time-to-routable for the launched replicas, and the zero-drop
   acceptance: every request of the whole ramp completes with the
   reference bytes.
2. ``elastic_resize_accounting`` — the goodput contract for scheduled
   resizes vs crashes: a scheduled resize books ONLY downtime into its
   own bucket (``resizes``/``resize_lost_s``; lost_steps == 0 because the
   resize checkpoint is synchronous at the boundary), while a crash books
   cadence-predicted lost steps into the crash bucket. Smoke verifies the
   accounting math on synthetic heartbeats; the full mode's subprocess
   fleet drill lives in tests/framework/test_elastic_resize.py.

Runs on any backend; CPU is the honest configuration (control-loop and
accounting behaviour are the quantities under test):

  JAX_PLATFORMS=cpu python tools/bench_elastic.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

# runnable as `python tools/bench_elastic.py` from the repo root
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _hist(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return {'count': 0, 'mean': None}
    count = sum(s.get('count', 0) for s in d['samples'])
    total = sum(s.get('sum', 0.0) for s in d['samples'])
    return {'count': count,
            'mean': round(total / count, 4) if count else None}


class _Replica:
    """In-process replica stack + HTTP listener."""

    def __init__(self, model, lock, rid, warm=True):
        from paddle_tpu.serving import ServingServer
        from paddle_tpu.serving.tier.replica import build_replica_stack
        self.engine, self.scheduler, _ = build_replica_stack(
            model=model, model_lock=lock, replica_id=rid)
        if warm:
            self.engine.warmup()
        self.server = ServingServer(None, port=0,
                                    generator=self.scheduler).start()
        self.url = f'http://127.0.0.1:{self.server.port}'

    def shutdown(self, drain=True):
        self.scheduler.close(drain=drain, timeout=30)
        self.server.shutdown(drain=drain)


def bench_autoscale_ramp(smoke):
    from paddle_tpu.dygraph import guard
    from paddle_tpu.elastic.autoscaler import AutoscaleConfig, Autoscaler
    from paddle_tpu.elastic.launcher import CallableReplicaLauncher
    from paddle_tpu.models.causal_lm import greedy_generate
    from paddle_tpu.observability import distributed as _dobs
    from paddle_tpu.serving import Router
    from paddle_tpu.serving.tier.replica import build_tiny_lm

    # short signal windows so the ramp-DOWN half of the drill sees the
    # load fall off within bench time (production default: 6 x 10s)
    for name in ('queue_depth', 'occupancy', 'ttft'):
        _dobs.series(name, window_s=1.0, windows=3)

    with guard():
        lm = build_tiny_lm()
    lock = threading.RLock()
    replicas = {}
    n = [0]

    def launch():
        n[0] += 1
        rep = _Replica(lm, lock, f'auto-{n[0]}', warm=False)
        replicas[rep.url] = rep
        # cold start on a thread: the warmup gate (not the launcher)
        # holds traffic until the compile cliff is behind the replica
        threading.Thread(target=rep.engine.warmup, daemon=True).start()
        return rep.url

    def retire(url):
        replicas.pop(url).shutdown()

    seed = _Replica(lm, lock, 'auto-0', warm=True)
    replicas[seed.url] = seed
    launcher = CallableReplicaLauncher(launch, retire)
    router = Router([seed.url], health_poll_s=0.25)
    cfg = AutoscaleConfig(
        min_replicas=1, max_replicas=2 if smoke else 3,
        interval_s=0.2, up_queue=1.0, up_ttft_s=60.0,
        down_occupancy=0.25, cooldown_s=1.5, down_delay_s=2.0)
    scaler = Autoscaler(router, launcher, cfg)

    prompt = [5, 9, 2, 44]
    new_tokens = 4
    ref = greedy_generate(lm, prompt, new_tokens,
                          pad_len=seed.engine.padded_context)
    results, errors = [], []
    results_lock = threading.Lock()

    def one_request():
        try:
            r = router.generate(prompt, max_new_tokens=new_tokens,
                                timeout=60)
            with results_lock:
                results.append(r)
        except Exception as e:   # noqa: BLE001 — drops are the metric
            with results_lock:
                errors.append(f'{type(e).__name__}: {e}')

    # open-loop Poisson arrivals: low -> high -> zero. The high rate has to
    # queue requests behind one replica's two slots: an engine call of the
    # tiny model is one ~1 ms program, a 4-token request ~5 ms
    rng = np.random.default_rng(0)
    phases = ([(2.0, 1.5), (80.0, 3.0)] if smoke
              else [(2.0, 3.0), (100.0, 6.0)])
    arrivals, t = [], 0.0
    for rate, dur in phases:
        end = t + dur
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= end:
                t = end
                break
            arrivals.append(t)

    count_samples = []           # (t, replicas, routable)
    stop_sampling = threading.Event()

    def sampler():
        t0 = time.monotonic()
        while not stop_sampling.wait(0.25):
            reps = list(router.replicas)
            count_samples.append(
                (round(time.monotonic() - t0, 2), len(reps),
                 sum(r.routable() for r in reps)))

    threading.Thread(target=sampler, daemon=True).start()
    workers = []
    t0 = time.monotonic()
    for at in arrivals:
        delay = t0 + at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        w = threading.Thread(target=one_request)
        w.start()
        workers.append(w)
    for w in workers:
        w.join(120)
    # idle tail: let sustained-low drain the tier back to min
    deadline = time.monotonic() + (10 if smoke else 20)
    while time.monotonic() < deadline and len(router.replicas) > 1:
        time.sleep(0.25)
    stop_sampling.set()
    decisions = [{'action': d['action'], 'trigger': d['trigger'],
                  'replicas': d['replicas']} for d in scaler.decisions]
    max_reps = max((c[1] for c in count_samples), default=1)
    final_reps = len(router.replicas)
    bitwise = all(r['tokens'] == ref for r in results)
    scaler.close()
    router.close()
    for rep in list(replicas.values()):
        try:
            rep.shutdown()
        except Exception:
            pass
    out = {
        'bench': 'elastic_autoscale_ramp',
        'requests': len(arrivals),
        'completed': len(results),
        'dropped': len(arrivals) - len(results),
        'errors': errors[:5],
        'bitwise_equal': bool(bitwise),
        'max_replicas_seen': max_reps,
        'max_replicas_cap': cfg.max_replicas,
        'final_replicas': final_reps,
        'scaled_up': any(d['action'] == 'up' for d in decisions),
        'scaled_down': any(d['action'] == 'down' for d in decisions),
        'decisions': decisions,
        'time_to_routable_s': _hist('autoscale_time_to_routable_seconds'),
        'drain_s': _hist('autoscale_drain_seconds'),
        'replica_count_timeline': count_samples[:: max(
            1, len(count_samples) // 24)],
    }
    assert out['dropped'] == 0 and not errors, (out['dropped'], errors[:3])
    assert bitwise
    assert out['scaled_up'] and max_reps > 1
    assert max_reps <= cfg.max_replicas
    assert all(d['trigger'] for d in decisions)
    return out


def bench_resize_accounting(smoke):
    """Goodput bucket separation on synthetic heartbeats: the scheduled
    resize books pure downtime (zero lost steps — its checkpoint is
    synchronous AT the boundary); a crash at the same step books exactly
    the cadence-predicted replay."""
    from paddle_tpu.resilience.goodput import GoodputTracker
    cadence, crash_step = 5, 13
    ckpt_step = (crash_step // cadence) * cadence          # 10
    predicted_lost = crash_step - ckpt_step                # 3
    base = time.time()

    crash = GoodputTracker()
    crash.record_restart(
        {'steps': ckpt_step, 'productive_s': float(ckpt_step),
         'wall_s': float(crash_step) + 1.0},
        {'steps': crash_step, 'productive_s': float(crash_step),
         'wall_s': float(crash_step) + 1.5, 'unix_time': base - 7.0})

    resize = GoodputTracker()
    resize.record_restart(
        # a scheduled resize checkpoints the exit boundary itself
        {'steps': crash_step, 'productive_s': float(crash_step),
         'wall_s': float(crash_step) + 1.0},
        {'steps': crash_step, 'productive_s': float(crash_step),
         'wall_s': float(crash_step) + 1.0, 'unix_time': base - 7.0,
         'resize_exit': True})

    out = {
        'bench': 'elastic_resize_accounting',
        'cadence': cadence,
        'crash_step': crash_step,
        'predicted_lost_steps': predicted_lost,
        'crash': {'lost_steps': crash.lost_steps,
                  'lost_s': round(crash.lost_s, 3),
                  'resizes': crash.resizes,
                  'resize_lost_s': round(crash.resize_lost_s, 3)},
        'resize': {'lost_steps': resize.lost_steps,
                   'lost_s': round(resize.lost_s, 3),
                   'resizes': resize.resizes,
                   'resize_lost_s': round(resize.resize_lost_s, 3)},
        'buckets_separate': (
            crash.lost_steps == predicted_lost and crash.resizes == 0
            and resize.lost_steps == 0 and resize.resizes == 1
            and resize.resize_lost_s > 0.0),
        'fleet_drill': 'tests/framework/test_elastic_resize.py',
    }
    assert out['buckets_separate'], out
    return out


def measure_all(smoke=False):
    out = {}
    for fn in (bench_autoscale_ramp, bench_resize_accounting):
        d = fn(smoke)
        out[d['bench']] = d
        print(json.dumps(d), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--smoke', action='store_true',
                    help='short phases, max 2 replicas (tier-1 CI gate)')
    args = ap.parse_args()
    measure_all(smoke=args.smoke)


if __name__ == '__main__':
    main()
