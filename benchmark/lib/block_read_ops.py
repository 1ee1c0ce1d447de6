"""Device time of a window model's block read by `jax.named_scope`, and the
work of the engine steps that ran in the same slice: what lib/scoped_ops.py
does for PR 26's scopes, for the one this file names (that file lists its
scopes and work keys by name and may not be edited).

The program names the read at its call site (paddle_tpu/serving/decode/
kv_cache.py::CacheContext.attend): `kv/block_read`, the gather of the live
groups' K and V rows, the scores, the running softmax and the weighted sum
of every slot's B rows. With telemetry on, each `engine/step` span of a
window model carries `window` (B) and `context_positions` (live positions
the step's reads attended, the block's own included, summed over layers);
steps alone are counted, a prefill attends its own prompt and reads no
block. The slice's bounds and the decoded planes come from
lib/scoped_ops.py::_slice and lib/xplane.py, called, not copied.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without this scope or these args (the
parent of the PR that added them, another model's cell)."""
import time

SCOPE = 'kv/block_read'


def _calls(lo_ns, hi_ns):
    """(live positions attended summed over the slice's window steps, those
    steps), a step in the slice if its midpoint is."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    positions = steps = 0
    for e in events:
        args = e.get('args') or {}
        if e.get('ph') != 'X' or e['name'] != 'engine/step' \
                or not args.get('window'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if lo_ns <= mid < hi_ns:
            steps += 1
            positions += args.get('context_positions', 0)
    return positions, steps


def reduce(run, ctx):
    """{'busy_s', 'seconds': device seconds of the scope's ops on chip 0 in
    the slice, 'positions', 'steps'}; kept in the run under `block_read_ops`
    for the second reader and for last_run.json."""
    if 'block_read_ops' in run:
        return run['block_read_ops']
    run['block_read_ops'] = None
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
    seconds = 0.0
    for _, a, b, stats in chips[0][1]['lines'][xplane.OP_LINE]:
        a, b = max(a, lo), min(b, hi)
        if b > a and f'/{SCOPE}/' in '/' + str(stats.get('tf_op') or '') + '/':
            seconds += (b - a) * 1e-12
    positions, steps = _calls(lo_ns, hi_ns)
    run['block_read_ops'] = {
        'busy_s': run['trace']['chips'][0]['busy_s'], 'seconds': seconds,
        'positions': positions, 'steps': steps}
    return run['block_read_ops']


def time_share(run, ctx):
    """Device seconds of the scope's ops over the chip's busy seconds, %."""
    found = reduce(run, ctx)
    if not found or not found['busy_s'] or not found['seconds']:
        return None
    return 100.0 * found['seconds'] / found['busy_s']


def roofline_share(run, ctx, flops, nbytes):
    """The least time the chip could take for (flops, nbytes), the larger of
    flops over its peak and bytes over its bandwidth, over the device
    seconds of the scope's ops, %."""
    found = reduce(run, ctx)
    peaks = run.get('peaks')
    if not found or not peaks or not found['seconds'] or not flops:
        return None
    least = max(flops / peaks['bf16_flops_per_s'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / found['seconds']
