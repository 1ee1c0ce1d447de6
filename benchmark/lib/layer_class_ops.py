"""Device time of the reads and prefill attentions of a model with layer
classes, by `jax.named_scope`, and the work of the engine calls that ran in
the same slice: what lib/scoped_ops.py does for PR 26's scopes and
lib/block_read_ops.py for PR 32's, for the four this file names (those
files list their scopes and work keys by name and may not be edited).

The program names each at its call site (paddle_tpu/serving/decode/
kv_cache.py::CacheContext._attend_class): `kv/decode_read` a full layer's
one-token read of a slot's table, `kv/sliding_read` a sliding layer's of its
ring, `attn/full_prefill` and `attn/sliding_prefill` the causal grouped
attention over a prompt's own projections. With telemetry on, each
`engine/step` span of such a model carries `full_positions` and
`sliding_positions` (live positions its reads attended, summed over the
class's layers: the context a full layer, min(context, span) a sliding one),
and each `engine/prefill` span its `prompt_len`. On the chip the prefill
attention itself is the stock pallas splash-attention kernel
(ops/nn_ops.py::_splash_prefill_attention), whose custom call the compiler
leaves WITHOUT an `op_name` (seen in the program compiled for a v5e): its
device ops are found by the kernel's own name in the event's name, `KERNEL`,
and booked under `attn/prefill_kernel`, one of the `PREFILL` scopes; the ops
around it (the query's scaling, copies) carry the call site's scope as
usual. The slice's bounds and the decoded planes come from
lib/scoped_ops.py::_slice and lib/xplane.py, called, not copied.

Everything returns None where there is nothing to read: no device trace (a
CPU rehearsal), no marks, a program without these scopes or args (the parent
of the PR that added them, another model's cell)."""
import time

SCOPES = ('kv/decode_read', 'kv/sliding_read', 'attn/full_prefill',
          'attn/sliding_prefill')
# what names the splash-attention kernel in a device op's event name (the
# instruction is `%vmap_jit__splash_attention__.N` in the engine's program,
# `%splash_mqa_fwd_no_residuals.N` where the kernel is called bare), and the
# key its seconds are booked under
KERNEL, KERNEL_SCOPE = 'splash', 'attn/prefill_kernel'
PREFILL = ('attn/full_prefill', 'attn/sliding_prefill', KERNEL_SCOPE)


def _calls(lo_ns, hi_ns):
    """{'full_positions', 'sliding_positions': sums over the slice's steps,
    'prompt_lens': of its prefills, 'steps'}; a call is in the slice if its
    midpoint is. Only calls of a model with layer classes count (they alone
    carry `full_positions`)."""
    from paddle_tpu import observability as obs
    now = time.perf_counter()
    obs.tracer.instant('bench_sync')
    events = obs.tracer.snapshot()['traceEvents']
    sync = next(e for e in reversed(events) if e['name'] == 'bench_sync')
    epoch_ns = now * 1e9 - sync['ts'] * 1e3
    work = {'full_positions': 0, 'sliding_positions': 0, 'prompt_lens': [],
            'steps': 0}
    for e in events:
        args = e.get('args') or {}
        if e.get('ph') != 'X' or 'full_positions' not in args \
                or e['name'] not in ('engine/step', 'engine/prefill'):
            continue
        mid = epoch_ns + (e['ts'] + e['dur'] / 2) * 1e3
        if not lo_ns <= mid < hi_ns:
            continue
        if e['name'] == 'engine/prefill':
            work['prompt_lens'].append(args.get('prompt_len', 0))
        else:
            work['steps'] += 1
            work['full_positions'] += args['full_positions']
            work['sliding_positions'] += args.get('sliding_positions', 0)
    return work


def reduce(run, ctx):
    """{'busy_s', 'scopes': {scope: device seconds on chip 0 in the slice},
    'work': `_calls`}; kept in the run under `layer_class_ops` for the other
    readers and for last_run.json."""
    if 'layer_class_ops' in run:
        return run['layer_class_ops']
    run['layer_class_ops'] = None
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
    scopes = dict.fromkeys(SCOPES + (KERNEL_SCOPE,), 0.0)
    for name, a, b, stats in chips[0][1]['lines'][xplane.OP_LINE]:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if KERNEL in str(name):
            scopes[KERNEL_SCOPE] += (b - a) * 1e-12
            continue
        op_name = '/' + str(stats.get('tf_op') or '') + '/'
        for scope in SCOPES:
            if f'/{scope}/' in op_name:
                scopes[scope] += (b - a) * 1e-12
                break
    run['layer_class_ops'] = {
        'busy_s': run['trace']['chips'][0]['busy_s'], 'scopes': scopes,
        'work': _calls(lo_ns, hi_ns)}
    return run['layer_class_ops']


def _seconds(found, scopes):
    return sum(found['scopes'][s] for s in scopes)


def time_share(run, ctx, scopes):
    """Device seconds of the ops under `scopes` over the chip's busy
    seconds, %."""
    found = reduce(run, ctx)
    if not found or not found['busy_s'] or not _seconds(found, scopes):
        return None
    return 100.0 * _seconds(found, scopes) / found['busy_s']


def roofline_share(run, ctx, scopes, flops, nbytes):
    """The least time the chip could take for (flops, nbytes), the larger of
    flops over its peak and bytes over its bandwidth, over the device
    seconds of the ops under `scopes`, %."""
    found = reduce(run, ctx)
    peaks = run.get('peaks')
    if not found or not peaks or not _seconds(found, scopes) or not flops:
        return None
    least = max(flops / peaks['bf16_flops_per_s'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / _seconds(found, scopes)
