"""Device time of the Mamba-2 mixer by `jax.named_scope`, and the work of
the engine calls that ran in the same slice: what lib/conv_mixer_ops.py
does for the gated short convolution, for the two scopes this file names
(that file lists its scopes and its span arg by name and may not be
edited).

The program names the mixer at its call site (paddle_tpu/models/
hybrid_ssm_moe_lm.py::MambaMixer.forward), whole from `W_in` to `W_out`:
`ssm/prefill` over a prompt's rung, `ssm/step` over a decode step's slots.
With telemetry on, each `engine/prefill` and `engine/step` span of such a
model carries `ssm_rows`: the call's LIVE rows x Mamba layers (a prefill's
prompt length, never its rung; a step's live slots). A step is bound by the
mixer's weights and the state rows, a prefill by FLOPs: the two scopes are
kept apart, and each call is priced for what binds IT (`least_seconds`).
The slice's bounds and the decoded planes come from lib/scoped_ops.py::
_slice and lib/xplane.py, called, not copied.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without these scopes or args (the parent
of the PR that added them, another model's cell)."""
import time

SCOPES = {'step': 'ssm/step', 'prefill': 'ssm/prefill'}


def _calls(lo_ns, hi_ns):
    """[(is a step, ssm_rows)] of the engine calls in the slice that carry
    `ssm_rows`; a call is in the slice if its midpoint is."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    calls = []
    for e in events:
        args = e.get('args') or {}
        if e.get('ph') != 'X' or 'ssm_rows' not in args \
                or e['name'] not in ('engine/prefill', 'engine/step'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if lo_ns <= mid < hi_ns:
            calls.append((e['name'] == 'engine/step', args['ssm_rows']))
    return calls


def reduce(run, ctx):
    """{'busy_s', 'scopes': {'step'|'prefill': device seconds on chip 0 in
    the slice}, 'calls': `_calls`}; kept in the run under `ssm_mixer_ops`
    for the other readers and for last_run.json."""
    if 'ssm_mixer_ops' in run:
        return run['ssm_mixer_ops']
    run['ssm_mixer_ops'] = None
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
    seconds = dict.fromkeys(SCOPES, 0.0)
    for _, a, b, stats in chips[0][1]['lines'][xplane.OP_LINE]:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        op_name = '/' + str(stats.get('tf_op') or '') + '/'
        for kind, scope in SCOPES.items():
            if f'/{scope}/' in op_name:
                seconds[kind] += (b - a) * 1e-12
                break
    run['ssm_mixer_ops'] = {
        'busy_s': run['trace']['chips'][0]['busy_s'], 'scopes': seconds,
        'calls': _calls(lo_ns, hi_ns)}
    return run['ssm_mixer_ops']


def time_share(run, ctx, kind):
    """Device seconds of the ops under the ``kind`` scope ('step' or
    'prefill') over the chip's busy seconds, %."""
    found = reduce(run, ctx)
    if not found or not found['busy_s'] or not found['scopes'][kind]:
        return None
    return 100.0 * found['scopes'][kind] / found['busy_s']


def least_seconds(calls, peaks, work):
    """The least time the chip could take for ``calls``: each call's own
    larger of FLOPs over the peak and bytes over the bandwidth,
    ``work(ssm_rows)`` -> (FLOPs, bytes), summed."""
    return sum(max(flops / peaks['bf16_flops_per_s'],
                   nbytes / peaks['hbm_bytes_per_s'])
               for flops, nbytes in (work(rows) for _, rows in calls))


def roofline_share(run, ctx, kind, work):
    """`least_seconds` of the slice's calls of ``kind`` over the device
    seconds of the ops under its scope, %."""
    found = reduce(run, ctx)
    peaks = run.get('peaks')
    if not found or not peaks or not found['scopes'][kind]:
        return None
    calls = [c for c in found['calls'] if c[0] == (kind == 'step')]
    if not calls:
        return None
    return 100.0 * least_seconds(calls, peaks, work) \
        / found['scopes'][kind]
