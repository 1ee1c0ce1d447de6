"""Serving-tier autoscaler (ISSUE 19): the control loop over REAL
in-process replicas — ramp up under load (queue/TTFT triggers, capped at
max), cold-replica warmup gating on scale-up (never routes cold, fast
admission poll), drain-then-retire on scale-down (zero drops), hysteresis
bounds, and the decision journal / autoscale_* metrics."""
import threading
import time

import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.elastic.autoscaler import AutoscaleConfig, Autoscaler
from paddle_tpu.elastic.launcher import CallableReplicaLauncher
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import Router, ServingServer
from paddle_tpu.serving.tier import knobs
from paddle_tpu.serving.tier.replica import build_replica_stack, build_tiny_lm


@pytest.fixture(scope='module')
def lm():
    with guard():
        yield build_tiny_lm()


class _InProcReplica:
    def __init__(self, lm, model_lock, replica_id, warm=True):
        self.engine, self.scheduler, _ = build_replica_stack(
            model=lm, model_lock=model_lock, replica_id=replica_id)
        if warm:
            self.engine.warmup()
        self.server = ServingServer(None, port=0,
                                    generator=self.scheduler).start()
        self.url = f'http://127.0.0.1:{self.server.port}'

    def shutdown(self, drain=True):
        self.scheduler.close(drain=drain, timeout=10)
        self.server.shutdown(drain=drain)


def _counter(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


def _wait_until(pred, timeout=30.0, poll=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return False


# -- config knobs ----------------------------------------------------------

def test_autoscale_config_strict_parse(monkeypatch):
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_MIN, 'two')
    with pytest.raises(ValueError, match=knobs.ENV_AUTOSCALE_MIN):
        AutoscaleConfig.from_env()
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_MIN, '0')
    with pytest.raises(ValueError, match='>= 1'):
        AutoscaleConfig.from_env()
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_MIN, '5')
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_MAX, '2')
    with pytest.raises(ValueError, match=knobs.ENV_AUTOSCALE_MAX):
        AutoscaleConfig.from_env()
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_MAX, '8')
    monkeypatch.setenv(knobs.ENV_AUTOSCALE_UP_QUEUE, '6.5')
    cfg = AutoscaleConfig.from_env()
    assert (cfg.min_replicas, cfg.max_replicas, cfg.up_queue) == (5, 8, 6.5)
    monkeypatch.delenv(knobs.ENV_AUTOSCALE, raising=False)
    assert AutoscaleConfig.enabled_from_env() is False
    monkeypatch.setenv(knobs.ENV_AUTOSCALE, '1')
    assert AutoscaleConfig.enabled_from_env() is True
    monkeypatch.setenv(knobs.ENV_AUTOSCALE, 'maybe')
    with pytest.raises(ValueError, match=knobs.ENV_AUTOSCALE):
        AutoscaleConfig.enabled_from_env()


# -- router elastic membership ---------------------------------------------

def test_add_replica_dedup_and_remove_unknown():
    router = Router(['http://127.0.0.1:1'], health_poll_s=60, start=False)
    try:
        assert len(router.replicas) == 1
        rep = router.add_replica('http://127.0.0.1:1/', fast_poll=False)
        assert rep is router.replicas[0]          # dedup, no second entry
        assert len(router.replicas) == 1
        router.add_replica('http://127.0.0.1:2', fast_poll=False)
        assert len(router.replicas) == 2
        router.remove_replica('http://127.0.0.1:2/')
        assert len(router.replicas) == 1
        with pytest.raises(KeyError):
            router.remove_replica('http://127.0.0.1:2')
    finally:
        router.close()


# -- the ramp drill --------------------------------------------------------

def test_autoscaler_ramp_up_and_down_zero_drops(lm):
    """Load ramp against a 1-replica tier: the autoscaler grows to max on
    queue/TTFT pressure (each new replica admitted only once warm), then
    drains back to min when sustained-low — with every request across the
    whole ramp completing with the reference bytes."""
    lock = threading.RLock()
    replicas = {}                  # url -> _InProcReplica
    n_launched = [0]

    def launch():
        n_launched[0] += 1
        rep = _InProcReplica(lm, lock, f'auto-{n_launched[0]}', warm=False)
        replicas[rep.url] = rep
        return rep.url

    def retire(url):
        replicas.pop(url).shutdown()

    seed = _InProcReplica(lm, lock, 'auto-0', warm=True)
    replicas[seed.url] = seed
    launcher = CallableReplicaLauncher(launch, retire)
    router = Router([seed.url], health_poll_s=60, start=False)
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=3, cooldown_s=5.0,
                          up_queue=2.0, up_ttft_s=1.0, down_occupancy=0.25,
                          down_delay_s=10.0)
    scaler = Autoscaler(router, launcher, cfg, start=False)

    prompt = [5, 9, 2, 44]
    ref = greedy_generate(lm, prompt, 4, pad_len=seed.engine.padded_context)
    results, errors = [], []

    def one_request():
        try:
            results.append(router.generate(prompt, max_new_tokens=4))
        except Exception as e:   # noqa: BLE001 — the drill counts drops
            errors.append(e)

    def stuff(**series):
        # scripted decision inputs (the windowed series are process-wide
        # in-proc, so per-replica signals are injected, not scraped)
        for r in router.replicas:
            if r.routable():
                r.series = {k: dict(v) for k, v in series.items()}

    try:
        # ---- ramp up: queue pressure → up #1, capped cold gate ----------
        router.poll_once()
        stuff(queue_depth={'mean': 8.0})
        d1 = scaler.tick(now=100.0)
        assert d1 and (d1['action'], d1['trigger']) == ('up', 'queue_depth')
        assert len(router.replicas) == 2 and len(launcher.launched) == 1
        new_url = launcher.launched[0]
        cold = router._replica_by_url(new_url)
        router.poll_once()
        # the warmup gate: launched cold, polled, still NOT routable
        assert cold.healthy and not cold.warmed and not cold.routable()
        # traffic while one replica is cold lands only on warm replicas
        threads = [threading.Thread(target=one_request) for _ in range(4)]
        [t.start() for t in threads]
        [t.join(30) for t in threads]
        assert not errors, errors
        assert all(r['replica'] == seed.url for r in results[-4:])

        # warmup completes → the FAST admission poll flips it routable in
        # well under the 60s regular poll period (satellite: short initial
        # backoff, time-to-routable not quantized to the poll interval)
        replicas[new_url].engine.warmup()
        t_warm = time.monotonic()
        assert _wait_until(cold.routable, timeout=20), cold.url
        assert time.monotonic() - t_warm < 10.0

        # ---- up #2 on TTFT SLO pressure, then the max_replicas cap ------
        stuff(queue_depth={'mean': 0.5}, ttft={'p99': 3.0})
        d2 = scaler.tick(now=106.0)
        assert d2 and (d2['action'], d2['trigger']) == ('up', 'ttft_p99')
        assert len(router.replicas) == 3
        third = launcher.launched[1]
        replicas[third].engine.warmup()
        assert _wait_until(router._replica_by_url(third).routable,
                           timeout=20)
        stuff(queue_depth={'mean': 9.0}, ttft={'p99': 3.0})
        assert scaler.tick(now=112.0) is None          # at max: no decision
        assert len(router.replicas) == 3 == cfg.max_replicas

        # burst across the full tier — every request completes, bitwise
        threads = [threading.Thread(target=one_request) for _ in range(8)]
        [t.start() for t in threads]
        [t.join(60) for t in threads]
        assert not errors, errors
        assert all(r['tokens'] == ref for r in results), results

        # ---- ramp down: sustained low → drain → retire, twice -----------
        router.poll_once()
        stuff(queue_depth={'mean': 0.0}, occupancy={'mean': 0.0})
        assert scaler.tick(now=200.0) is None          # low_since arming
        d3 = scaler.tick(now=211.0)                    # sustained >= 10s
        assert d3 and (d3['action'], d3['trigger']) == ('down', 'occupancy')
        victim1 = d3['url']
        assert router._replica_by_url(victim1).draining
        assert scaler.draining() == [victim1]
        router.poll_once()                             # observe empty queue
        stuff(queue_depth={'mean': 0.0}, occupancy={'mean': 0.0})
        scaler.tick(now=212.0)                         # drained → retired
        assert launcher.retired == [victim1]
        assert len(router.replicas) == 2
        stuff(queue_depth={'mean': 0.0}, occupancy={'mean': 0.0})
        d4 = scaler.tick(now=223.0)
        assert d4 and d4['action'] == 'down'
        router.poll_once()
        scaler.tick(now=224.0)
        assert len(router.replicas) == 1 == cfg.min_replicas
        assert len(launcher.retired) == 2
        # floor: no further scale-down below min_replicas
        stuff(queue_depth={'mean': 0.0}, occupancy={'mean': 0.0})
        assert scaler.tick(now=300.0) is None

        # a request through the shrunk tier still completes — zero drops
        # across the whole ramp, scale-down included
        one_request()
        assert not errors, errors
        assert results[-1]['tokens'] == ref

        # ---- the journal + metrics: every decision recorded, with its
        # trigger
        acts = [(d['action'], d['trigger']) for d in scaler.decisions]
        assert acts == [('up', 'queue_depth'), ('up', 'ttft_p99'),
                        ('down', 'occupancy'), ('down', 'occupancy')]
        assert all('signals' in d and 'unix_time' in d
                   for d in scaler.decisions)
        assert _counter('autoscale_decisions') >= 4

        def hist_count(name):
            from paddle_tpu.observability import registry
            d = registry.to_dict().get(name)
            return sum(s.get('count', 0) for s in d['samples']) if d else 0

        assert hist_count('autoscale_time_to_routable_seconds') >= 2
        assert hist_count('autoscale_drain_seconds') >= 2
    finally:
        scaler.close()
        router.close()
        for rep in list(replicas.values()):
            try:
                rep.shutdown()
            except Exception:
                pass


def test_control_loop_thread_follows_a_closed_loop_load(lm):
    """The control loop on its own thread, deciding from the REAL windowed
    series (the test above scripts both): eight closed-loop clients on a
    two-slot replica keep requests queued, a depth no CPU speed changes,
    so the tier grows, the cold replica joining behind the warmup gate;
    the clients stop and sustained low occupancy drains it back to one.
    Every request of the run completes with the reference bytes, the
    replica count stays within the cap, every decision names its trigger.
    The deadlines are hang guards."""
    from paddle_tpu.observability import distributed as dobs
    dobs.reset_distributed()
    # short signal windows, so the load's end shows inside the test
    # (production default: 6 x 10 s)
    for name in ('queue_depth', 'occupancy', 'ttft'):
        dobs.series(name, window_s=1.0, windows=3)
    lock = threading.RLock()
    replicas, launched = {}, []

    def launch():
        rep = _InProcReplica(lm, lock, f'loop-{len(launched) + 1}',
                             warm=False)
        replicas[rep.url] = rep
        launched.append(rep.url)
        # cold start on a thread: the warmup gate holds traffic off it
        threading.Thread(target=rep.engine.warmup, daemon=True).start()
        return rep.url

    def retire(url):
        replicas.pop(url).shutdown()

    seed = _InProcReplica(lm, lock, 'loop-0', warm=True)
    replicas[seed.url] = seed
    router = Router([seed.url], health_poll_s=0.25)
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2, interval_s=0.2,
                          up_queue=1.0, up_ttft_s=60.0, down_occupancy=0.25,
                          cooldown_s=1.5, down_delay_s=2.0)
    scaler = Autoscaler(router, CallableReplicaLauncher(launch, retire), cfg)

    prompt = [5, 9, 2, 44]
    ref = greedy_generate(lm, prompt, 4, pad_len=seed.engine.padded_context)
    results, errors, sizes = [], [], []
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                results.append(router.generate(prompt, max_new_tokens=4,
                                               timeout=60))
            except Exception as e:   # noqa: BLE001 — the drill counts drops
                errors.append(e)
            sizes.append(len(router.replicas))

    clients = [threading.Thread(target=client) for _ in range(8)]
    try:
        for t in clients:
            t.start()
        # launch() returns before the scaler hands the url to the router
        assert _wait_until(
            lambda: any(r.url in launched and r.routable()
                        for r in router.replicas), timeout=90), \
            scaler.decisions
        answered = len(results)
        assert _wait_until(lambda: len(results) >= answered + 16, timeout=60)
        stop.set()
        for t in clients:
            t.join(90)
        assert not any(t.is_alive() for t in clients)
        assert _wait_until(lambda: len(router.replicas) == 1, timeout=90), \
            scaler.decisions
    finally:
        stop.set()
        scaler.close()
        router.close()
        for rep in list(replicas.values()):
            try:
                rep.shutdown()
            except Exception:
                pass
        dobs.reset_distributed()
    assert not errors, errors[:3]
    assert results and all(r['tokens'] == ref for r in results)
    assert {r['replica'] for r in results} == {seed.url, launched[0]}
    acts = [d['action'] for d in scaler.decisions]
    assert 'up' in acts and 'down' in acts
    assert all(d['trigger'] for d in scaler.decisions)
    assert 1 < max(sizes) <= cfg.max_replicas


def test_autoscaler_min_replicas_floor_spawns():
    """Below min_replicas the scaler launches unconditionally (cold tier
    bring-up), trigger recorded as min_replicas."""
    calls = []
    launcher = CallableReplicaLauncher(
        lambda: calls.append(1) or f'http://127.0.0.1:{len(calls)}',
        lambda url: None)
    router = Router(['http://127.0.0.1:1'], health_poll_s=60, start=False)
    router.remove_replica('http://127.0.0.1:1')
    cfg = AutoscaleConfig(min_replicas=2, max_replicas=3, cooldown_s=0.0)
    scaler = Autoscaler(router, launcher, cfg, start=False)
    try:
        d = scaler.tick(now=1.0)
        assert d and d['trigger'] == 'min_replicas'
        d = scaler.tick(now=2.0)
        assert d and d['trigger'] == 'min_replicas'
        assert len(router.replicas) == 2
        assert scaler.tick(now=3.0) is None       # floor satisfied
    finally:
        scaler.close()
        router.close()


def test_process_launcher_failure_carries_child_stderr():
    """A replica process that dies before its ready line (on a one-chip
    machine: the second replica cannot get the chip) must say why — the
    launch error carries the tail of the child's stderr."""
    from paddle_tpu.elastic.launcher import ProcessReplicaLauncher
    launcher = ProcessReplicaLauncher(extra_args=['--no-such-flag'],
                                      ready_timeout_s=60)
    with pytest.raises(RuntimeError) as ei:
        launcher.launch()
    msg = str(ei.value)
    assert 'replica launch failed' in msg
    assert 'child stderr tail' in msg and '--no-such-flag' in msg, msg
