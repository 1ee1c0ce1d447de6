"""The decode replica accounted for from inside: what its phase histograms
(always on, so the same in a traced and an untraced run) and its per-call
spans (telemetry on) say, for the per-layer readers that share them.

decode_engine_phase_seconds{call, phase} tiles every engine call into pack,
forward, device_wait, logits_copy and sample; decode_scheduler_phase_seconds
{phase} books the worker thread's loop iterations whole (cycle) and by part
(admit, engine, emit, wait), all of one iteration at its end, so that a
window's edge never separates a cycle from the engine time inside it. The
spans engine/<call>/<phase>, scheduler/admit and scheduler/emit are the
leaves of the worker thread's span tree."""

ENGINE = 'decode_engine_phase_seconds'
SCHEDULER = 'decode_scheduler_phase_seconds'

# innermost spans of the worker thread, the likeliest owner of a gap first
LEAVES = [f'engine/{call}/{phase}'
          for phase in ('forward', 'device_wait', 'logits_copy', 'sample',
                        'pack')
          for call in ('prefill', 'step', 'spec_step')] \
    + ['scheduler/emit', 'scheduler/admit']


def sums(run, name, label):
    """{value of `label`: seconds} of a labelled program histogram, summed
    over its other labels; None where the program recorded none."""
    metric = (run.get('registry') or {}).get(name)
    if not metric or not metric['samples']:
        return None
    out = {}
    for sample in metric['samples']:
        key = sample['labels'].get(label)
        out[key] = out.get(key, 0.0) + sample['sum']
    return out


def counter(run, name):
    """A program counter's value over all of its label sets; None where the
    program has no such counter."""
    metric = (run.get('registry') or {}).get(name)
    if not metric or not metric['samples']:
        return None
    return sum(sample['value'] for sample in metric['samples'])


def engine_phase_share(run, phase):
    """Seconds of `phase` over the seconds of all phases, over every engine
    call of the window, in percent."""
    phases = sums(run, ENGINE, 'phase')
    total = sum(phases.values()) if phases else 0.0
    return 100.0 * phases.get(phase, 0.0) / total if total else None


def scheduler_self_share(run):
    """(cycle - wait - engine) / (cycle - wait) over the worker thread's
    iterations, in percent: its busy time outside engine calls."""
    phases = sums(run, SCHEDULER, 'phase')
    if not phases or 'cycle' not in phases:
        return None
    busy = phases['cycle'] - phases.get('wait', 0.0)
    if busy <= 0:
        return None
    return 100.0 * (busy - phases.get('engine', 0.0)) / busy


def idle_by_leaf(run, ctx):
    """{leaf span or 'no span': idle seconds of chip 0 in the traced slice},
    each idle gap given to the leaf whose span covers its midpoint. None
    where there is no device trace on the harness's clock, or the program
    recorded no such span (a program from before PR 24). Kept in the run
    under `idle_by_leaf`, for the second reader and for last_run.json."""
    if 'idle_by_leaf' in run:
        return run['idle_by_leaf']
    trace = run.get('trace')
    if not trace or trace.get('offset_ns') is None:
        return None
    gaps = trace['chips'][0].get('gaps')
    if not gaps:
        return None
    from paddle_tpu import observability as obs
    offset = trace['offset_ns']
    lo = min(a for a, _ in gaps) - offset
    hi = max(b for _, b in gaps) - offset
    spans = [s for s in ctx.module('lib', 'spans').program_spans(obs, LEAVES)
             if s[2] > lo and s[1] < hi]
    if not spans:
        return None
    run['idle_by_leaf'] = dict(ctx.xplane.attribute_gaps(
        gaps, spans, offset, LEAVES, top=len(LEAVES) + 1))
    return run['idle_by_leaf']
