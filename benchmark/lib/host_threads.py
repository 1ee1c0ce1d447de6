"""The serve host's threads seen from inside (PR 34): the reductions of the
five per-layer metrics that read the thread-CPU clock the program keeps
beside its wall clock.

decode_engine_phase_cpu_seconds{call, phase} and
decode_scheduler_phase_cpu_seconds{phase} are counters of time.thread_time()
over the very stamps of decode_engine_phase_seconds and
decode_scheduler_phase_seconds (lib/decode_phases.py). thread_time counts a
thread only while it runs, so wall - CPU of a phase is the time the worker
thread was OFF the CPU in it: waiting for the interpreter lock, for the
device, or in a blocking call. In the phases that never block of themselves
(NEVER_BLOCK: pack and sample of every call, admit, emit, book: interpreter
and numpy alone) it is the wait for the interpreter lock. `book` is the
worker's bookkeeping between the other leaves; with it the leaves tile a
cycle: cycle = admit + the engine calls' phases + emit + book + wait (what
lies between two cycles, a cycle's own observations, is a scheduler/book
span under no cycle and in no phase's sum). http_handler_cpu_seconds is the
handler threads' CPU, one increment a request.

What the sums are worth depends on the host. On a Linux kernel the thread
clock reads in nanoseconds. The chip's host is a gVisor sandbox (PERF.md
section 6, PR 34): its task clocks count ticks of 10 ms, and the sandbox
stops the ticker while no thread of the process runs and starts it at the
next wake-up, so the ticks are anchored to the worker's own wake-ups and a
phase that lies just after one is charged with its neighbours' ticks: it can
read more CPU than wall (`overcounted` names such phases; the reader of
worker_on_cpu_share prints them), and the neighbours too little. Where the
interpreter is busy throughout (128 handler threads) the ticker never stops,
and the phases' sums agreed with the same calls timed with no other thread
running. Nothing is cut off at 0 or at the wall: every share here is the
signed sum as the program counted it.

Every reduction returns None where the program recorded none of it (a
program from before PR 34), and raises nothing.
"""

ENGINE = 'decode_engine_phase_seconds'
ENGINE_CPU = 'decode_engine_phase_cpu_seconds'
SCHEDULER = 'decode_scheduler_phase_seconds'
SCHEDULER_CPU = 'decode_scheduler_phase_cpu_seconds'
HANDLER_CPU = 'http_handler_cpu_seconds'

CALLS = ('prefill', 'step', 'spec_step')
# the leaves in which the worker runs its own Python and never blocks
PYTHON = [f'engine/{call}/{phase}' for phase in ('sample', 'pack')
          for call in CALLS] \
    + ['scheduler/emit', 'scheduler/admit', 'scheduler/book']
# innermost spans of the worker thread, the likeliest owner of a gap first:
# lib/decode_phases.py's list with the bookkeeping leaf in it
LEAVES = [f'engine/{call}/{phase}'
          for phase in ('forward', 'device_wait', 'logits_copy')
          for call in CALLS] + PYTHON


def seconds(run, name, label):
    """{value of `label`: seconds} of a labelled program histogram (its
    sums) or counter (its values), summed over its other labels; None where
    the program recorded none."""
    metric = (run.get('registry') or {}).get(name)
    if not metric or not metric.get('samples'):
        return None
    out = {}
    for sample in metric['samples']:
        key = sample['labels'].get(label)
        out[key] = out.get(key, 0.0) + sample.get('sum',
                                                  sample.get('value', 0.0))
    return out


def phases(run):
    """{leaf or scheduler phase: (wall s, CPU s)} over the window: the
    engine's phases summed over its calls under `engine/<phase>`, the
    scheduler's under `scheduler/<phase>`. None without the CPU counters."""
    tables = [seconds(run, name, 'phase') for name in
              (ENGINE, ENGINE_CPU, SCHEDULER, SCHEDULER_CPU)]
    if any(t is None for t in tables):
        return None
    engine, engine_cpu, scheduler, scheduler_cpu = tables
    out = {f'engine/{p}': (s, engine_cpu.get(p, 0.0))
           for p, s in engine.items()}
    out.update({f'scheduler/{p}': (s, scheduler_cpu.get(p, 0.0))
                for p, s in scheduler.items()})
    return out


# the worker's phases in which it never blocks of itself
NEVER_BLOCK = ('engine/pack', 'engine/sample', 'scheduler/admit',
               'scheduler/emit', 'scheduler/book')


def overcounted(table, tick=0.010):
    """[(phase, wall s, CPU s)] of the phases that read more CPU seconds
    than wall seconds by more than the counting noise of a clock that
    advances in `tick`s (the root of the count, in ticks, and one tick):
    a thread cannot run for longer than the time that passed, so such a
    sum says the clock charged the phase with ticks of a neighbour's."""
    out = []
    for name, (wall, cpu) in sorted((table or {}).items()):
        noise = tick * (1.0 + max(cpu / tick, 0.0) ** 0.5)
        if cpu > wall + noise:
            out.append((name, wall, cpu))
    return out


def _busy(table):
    """The worker's busy wall seconds: its cycles less its waits."""
    if not table or 'scheduler/cycle' not in table:
        return None
    busy = table['scheduler/cycle'][0] \
        - table.get('scheduler/wait', (0.0, 0.0))[0]
    return busy if busy > 0 else None


def worker_on_cpu_share(run):
    """CPU seconds of the worker's cycles over its busy wall seconds, in
    percent: how much of a core its own work needs while it is busy."""
    table = phases(run)
    busy = _busy(table)
    if busy is None:
        return None
    return 100.0 * table['scheduler/cycle'][1] / busy


def worker_lock_wait_share(run):
    """wall - CPU over the phases that never block of themselves (pack and
    sample of every call; admit, emit, book), over the worker's busy wall
    seconds, in percent: its busy time spent waiting for the interpreter.
    A lower bound: waits inside forward are not in it. The signed sum: a
    phase that reads more CPU than wall (`overcounted`) takes from it what
    a neighbour was given too little of."""
    table = phases(run)
    busy = _busy(table)
    if busy is None:
        return None
    waited = sum(wall - cpu for wall, cpu in
                 (table.get(n, (0.0, 0.0)) for n in NEVER_BLOCK))
    return 100.0 * waited / busy


def engine_forward_offcpu_share(run):
    """wall - CPU of `forward` over its wall, over every call of the window,
    in percent: the part of a dispatch in which the worker does not run."""
    wall = (seconds(run, ENGINE, 'phase') or {}).get('forward', 0.0)
    cpu = seconds(run, ENGINE_CPU, 'phase')
    if cpu is None or wall <= 0:
        return None
    return 100.0 * (wall - cpu.get('forward', 0.0)) / wall


def http_handler_cpu_share(run):
    """The handler threads' CPU seconds over the seconds the registry
    covers, in percent of one core. The registry is emptied at the window's
    opening and read after its close; the worker's cycles fill the seconds
    between (idle ones too, and all but the microseconds that lie between
    two cycles), so their wall sum is the divisor: both sides come from one
    registry over one stretch of time. An upper bound on the interpreter
    held: the threads' system calls run without its lock, and inside a
    sandbox a system call is costly (PERF.md section 6, PR 34)."""
    handlers = seconds(run, HANDLER_CPU, None)
    cycles = seconds(run, SCHEDULER, 'phase')
    if not handlers or not cycles or not cycles.get('cycle'):
        return None
    return 100.0 * sum(handlers.values()) / cycles['cycle']


def idle_by_leaf(run, ctx):
    """{leaf span or 'no span': idle seconds of chip 0 in the traced slice},
    each idle gap given to the leaf of LEAVES whose span covers its
    midpoint. None where there is no device trace on the harness's clock or
    the program recorded no scheduler/book span (a program from before PR
    34: its leaves do not tile the worker). Kept in the run under
    `idle_by_host_leaf`, for last_run.json."""
    if 'idle_by_host_leaf' in run:
        return run['idle_by_host_leaf']
    trace = run.get('trace')
    if not trace or trace.get('offset_ns') is None:
        return None
    gaps = trace['chips'][0].get('gaps')
    if not gaps:
        return None
    from paddle_tpu import observability as obs
    if obs.tracer.dropped:
        ctx.info(f'span buffer full: {obs.tracer.dropped} events dropped of '
                 f'a bound of {obs.tracer.max_events}; the spans cover part '
                 'of the window, no idle share is read from them')
        return None
    offset = trace['offset_ns']
    lo = min(a for a, _ in gaps) - offset
    hi = max(b for _, b in gaps) - offset
    spans = [s for s in ctx.module('lib', 'spans').program_spans(obs, LEAVES)
             if s[2] > lo and s[1] < hi]
    if not any(name == 'scheduler/book' for name, _, _ in spans):
        return None
    run['idle_by_host_leaf'] = dict(ctx.xplane.attribute_gaps(
        gaps, spans, offset, LEAVES, top=len(LEAVES) + 1))
    return run['idle_by_host_leaf']


def idle_in_worker_python_share(run, ctx):
    """Share of chip 0's idle seconds under the PYTHON leaves, in percent:
    the device waiting while the worker is in its own Python."""
    idle = idle_by_leaf(run, ctx)
    if not idle:
        return None
    python = sum(idle.get(name, 0.0) for name in PYTHON)
    return 100.0 * python / sum(idle.values())
