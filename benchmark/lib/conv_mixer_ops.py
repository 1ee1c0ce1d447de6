"""Device time of the gated short convolution by `jax.named_scope`, and the
work of the engine calls that ran in the same slice: what lib/scoped_ops.py
does for PR 26's scopes, for the two this file names (that file lists its
scopes and work keys by name and may not be edited).

The program names the operator at its call site (paddle_tpu/models/
hybrid_conv_moe_lm.py::ShortConv.forward), whole from `W_in` to `W_out`:
`conv/prefill` over a prompt's rung, `conv/step` over a decode step's slots.
With telemetry on, each `engine/prefill` and `engine/step` span of such a
model carries `conv_rows`: the call's LIVE rows x conv layers (a prefill's
prompt length, never its rung; a step's live slots). A step is bound by the
operator's weights and a prefill by FLOPs, so the calls are kept one by one
and each is priced for what binds IT (`least_seconds`). The slice's bounds
and the decoded planes come from lib/scoped_ops.py::_slice and
lib/xplane.py, called, not copied.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without these scopes or args (the parent
of the PR that added them, another model's cell)."""
import time

SCOPES = ('conv/prefill', 'conv/step')


def _calls(lo_ns, hi_ns):
    """[(is a step, conv_rows)] of the engine calls in the slice that carry
    `conv_rows`; a call is in the slice if its midpoint is."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    calls = []
    for e in events:
        args = e.get('args') or {}
        if e.get('ph') != 'X' or 'conv_rows' not in args \
                or e['name'] not in ('engine/prefill', 'engine/step'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if lo_ns <= mid < hi_ns:
            calls.append((e['name'] == 'engine/step', args['conv_rows']))
    return calls


def reduce(run, ctx):
    """{'busy_s', 'scopes': {scope: device seconds on chip 0 in the slice},
    'calls': `_calls`}; kept in the run under `conv_mixer_ops` for the other
    reader and for last_run.json."""
    if 'conv_mixer_ops' in run:
        return run['conv_mixer_ops']
    run['conv_mixer_ops'] = None
    if not run.get('trace') or not getattr(ctx, 'trace_file', None):
        return None
    xplane = ctx.xplane
    planes = xplane._decode(
        ctx.trace_file, lambda plane, line: plane.startswith('/host:')
        or (xplane.DEVICE_PLANE.match(plane) and line == xplane.OP_LINE))
    bounds = ctx.module('lib', 'scoped_ops')._slice(planes, xplane)
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
    run['conv_mixer_ops'] = {
        'busy_s': run['trace']['chips'][0]['busy_s'], 'scopes': scopes,
        'calls': _calls(lo_ns, hi_ns)}
    return run['conv_mixer_ops']


def time_share(run, ctx):
    """Device seconds of the ops under the two scopes over the chip's busy
    seconds, %."""
    found = reduce(run, ctx)
    if not found or not found['busy_s'] or not sum(found['scopes'].values()):
        return None
    return 100.0 * sum(found['scopes'].values()) / found['busy_s']


def least_seconds(found, peaks, work):
    """The least time the chip could take for the slice's calls: each
    call's own larger of FLOPs over the peak and bytes over the bandwidth,
    ``work(conv_rows, step)`` -> (FLOPs, bytes), summed."""
    return sum(max(flops / peaks['bf16_flops_per_s'],
                   nbytes / peaks['hbm_bytes_per_s'])
               for flops, nbytes in (work(rows, step)
                                     for step, rows in found['calls']))


def roofline_share(run, ctx, work):
    """`least_seconds` over the device seconds of the ops under the two
    scopes, %."""
    found = reduce(run, ctx)
    peaks = run.get('peaks')
    seconds = sum(found['scopes'].values()) if found else 0
    if not found or not peaks or not seconds or not found['calls']:
        return None
    return 100.0 * least_seconds(found, peaks, work) / seconds
