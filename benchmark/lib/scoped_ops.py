"""Device time by `jax.named_scope`, and the work of the engine calls that
ran in the same slice.

The program names its distinctive device ops by scope (ops/llm_ops.py:
`moe/experts`, `mla/decode_read`, ...). A scope reaches the trace as part of
each op's `tf_op` (the HLO's op_name, e.g. `jit(run)/.../moe/experts/
ragged_dot`), in the op event's metadata, which lib/xplane.py::_decode
reads. A fusion carries the name of its root instruction.

A roofline share sets the time of a scope's ops in the traced slice against
the work of the engine calls in THAT slice, not of the window: with
telemetry on, each `engine/<call>` span's args carry the call's work
(assignments computed, experts given a row, context positions attended:
paddle_tpu/serving/decode/engine.py::_CallClock). A call belongs to the
slice if its midpoint does; a call cut by an edge is a call in ~25.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without these spans or scopes (the
parent of the PR that added them)."""
import time

SCOPES = ('moe/route', 'moe/experts', 'moe/shared', 'mla/prefill_attention',
          'mla/decode_read')
WORK = ('expert_assignments', 'experts_touched', 'context_positions')


def _slice(planes, xplane):
    """(lo_ps, hi_ps) on the trace's clock and (lo_ns, hi_ns) on
    perf_counter, from the harness's begin and end marks."""
    marks = {}
    for plane in planes:
        if plane['name'].startswith('/host:'):
            for events in plane['lines'].values():
                for name, start, _, _ in events:
                    if name.startswith(xplane.MARK):
                        label, perf_ns = name[len(xplane.MARK):].rsplit(':',
                                                                        1)
                        marks[label] = (start, int(perf_ns))
    if 'begin' not in marks or 'end' not in marks:
        return None
    return ((marks['begin'][0], marks['end'][0]),
            (marks['begin'][1], marks['end'][1]))


def _calls(lo_ns, hi_ns):
    """{work key: sum over the engine calls whose midpoint lies in the
    slice}, and how many calls that was."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    work, calls = dict.fromkeys(WORK, 0), 0
    for e in events:
        if e.get('ph') != 'X' or e['name'] not in (
                'engine/prefill', 'engine/step', 'engine/spec_step'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if lo_ns <= mid < hi_ns:
            calls += 1
            for key in WORK:
                work[key] += (e.get('args') or {}).get(key, 0)
    return work, calls


def reduce(run, ctx):
    """{'busy_s', 'scopes': {scope: device seconds on chip 0 in the slice},
    'work': {key: sum over the slice's engine calls}, 'calls'}; kept in the
    run under `scoped_ops` for the other readers and for last_run.json."""
    if 'scoped_ops' in run:
        return run['scoped_ops']
    run['scoped_ops'] = None
    if not run.get('trace') or not getattr(ctx, 'trace_file', None):
        return None
    xplane = ctx.xplane
    planes = xplane._decode(
        ctx.trace_file, lambda plane, line: plane.startswith('/host:')
        or (xplane.DEVICE_PLANE.match(plane) and line == xplane.OP_LINE))
    bounds = _slice(planes, xplane)
    chips = sorted((int(xplane.DEVICE_PLANE.match(p['name']).group(1)), p)
                   for p in planes if xplane.DEVICE_PLANE.match(p['name'])
                   and p['lines'].get(xplane.OP_LINE))
    if bounds is None or not chips:
        return None
    (lo, hi), (lo_ns, hi_ns) = bounds
    scopes = dict.fromkeys(SCOPES, 0.0)
    for _, a, b, stats in chips[0][1]['lines'][xplane.OP_LINE]:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        op_name = '/' + str(stats.get('tf_op') or '') + '/'
        for scope in SCOPES:
            if f'/{scope}/' in op_name:
                scopes[scope] += (b - a) * 1e-12
                break
    work, calls = _calls(lo_ns, hi_ns)
    run['scoped_ops'] = {'busy_s': run['trace']['chips'][0]['busy_s'],
                         'scopes': scopes, 'work': work, 'calls': calls}
    return run['scoped_ops']


def time_share(run, ctx, scope):
    """Device seconds of `scope`'s ops over the chip's busy seconds, %."""
    found = reduce(run, ctx)
    if not found or not found['busy_s'] or not found['scopes'][scope]:
        return None
    return 100.0 * found['scopes'][scope] / found['busy_s']


def roofline_share(run, ctx, scope, flops, nbytes):
    """The least time the chip could take for (flops, nbytes), the larger of
    flops over its peak and bytes over its bandwidth, over the device
    seconds of `scope`'s ops, %."""
    found = reduce(run, ctx)
    peaks = run.get('peaks')
    if not found or not peaks or not found['scopes'][scope] or not flops:
        return None
    least = max(flops / peaks['bf16_flops_per_s'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / found['scopes'][scope]
