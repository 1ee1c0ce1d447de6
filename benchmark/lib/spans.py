"""The program's own host spans, put on the clock the harness uses.

paddle_tpu.observability's tracer stamps microseconds from an epoch of its
own. One instant event taken beside a perf_counter reading gives the epoch
back, so that its spans can be laid against the device trace (whose marks
carry perf_counter_ns, lib/xplane.py)."""
import time


def program_spans(obs, names):
    """[(name, start_ns, end_ns)] on perf_counter, of the complete spans
    whose name is in `names`; spans sharing name and interval (one decode
    step seen by every traced request in it) are given once."""
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    return sorted({(e['name'], epoch_ns + e['ts'] * 1e3,
                    epoch_ns + (e['ts'] + e['dur']) * 1e3)
                   for e in events
                   if e.get('ph') == 'X' and e['name'] in names})
