"""Device time of the power-retention ops by `jax.named_scope`, and the work
of the engine calls that ran in the same slice: what lib/scoped_ops.py does
for PR 26's scopes, for the two this file names (that file lists its scopes
and work keys by name and may not be edited).

The program names the ops at their call sites (paddle_tpu/serving/decode/
kv_cache.py::CacheContext.attend_retention): `retention/prefill_scan`, the
chunked scan of a prefill, and `retention/decode_update`, a step's gate,
rank-one update and read of every slot's state. With telemetry on, each
`engine/step` span's args carry `state_updates` (live slot-layers the step
advanced) and each `engine/prefill` span's `state_tokens_folded` and
`prompt_len`; a prefill's work is priced by its own prompt length, so the
lengths are kept one by one. The slice's bounds and the decoded planes come
from lib/scoped_ops.py::_slice and lib/xplane.py, called, not copied.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without these spans or scopes (the
parent of the PR that added them, another model's cell)."""
import time

SCOPES = ('retention/prefill_scan', 'retention/decode_update')


def _calls(lo_ns, hi_ns):
    """(state updates summed over the slice's steps, [prompt length of each
    of the slice's prefills that folded tokens into a state], calls), a
    call in the slice if its midpoint is."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    updates, prompts, calls = 0, [], 0
    for e in events:
        if e.get('ph') != 'X' or e['name'] not in ('engine/prefill',
                                                   'engine/step'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if not lo_ns <= mid < hi_ns:
            continue
        calls += 1
        args = e.get('args') or {}
        updates += args.get('state_updates', 0)
        if args.get('state_tokens_folded'):
            prompts.append(args['prompt_len'])
    return updates, prompts, calls


def reduce(run, ctx):
    """{'busy_s', 'scopes': {scope: device seconds on chip 0 in the slice},
    'state_updates', 'prompt_lens', 'calls'}; kept in the run under
    `retention_ops` for the other readers and for last_run.json."""
    if 'retention_ops' in run:
        return run['retention_ops']
    run['retention_ops'] = None
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
    updates, prompts, calls = _calls(lo_ns, hi_ns)
    run['retention_ops'] = {
        'busy_s': run['trace']['chips'][0]['busy_s'], 'scopes': scopes,
        'state_updates': updates, 'prompt_lens': prompts, 'calls': calls}
    return run['retention_ops']


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
