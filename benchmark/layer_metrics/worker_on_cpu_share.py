"""Thread-CPU seconds of the scheduler worker's cycles over its busy wall
seconds (cycle - wait): decode_scheduler_phase_cpu_seconds{cycle} over the
sums of decode_scheduler_phase_seconds. How much of a core the worker's own
interpreter work needs while it is busy: what stays of the host's time when
nothing else holds the interpreter lock (lib/host_threads.py). The run's
[info] line gives every phase's wall and CPU seconds, and names the phases
that read more CPU than wall."""
NAME = 'worker_on_cpu_share'
LAYER = 'scheduler'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    host = ctx.module('lib', 'host_threads')
    table = host.phases(run)
    if table:
        ctx.info('worker phases, wall s / CPU s: ' + ', '.join(
            f'{name} {wall:.3f}/{cpu:.3f}'
            for name, (wall, cpu) in sorted(table.items())))
        over = host.overcounted(table)
        if over:
            ctx.info('MORE CPU THAN WALL, beyond the noise of a 10 ms tick '
                     '(the thread clock charged these phases with a '
                     "neighbour's ticks; the shares are signed sums): "
                     + ', '.join(f'{name} {wall:.3f}/{cpu:.3f}'
                                 for name, wall, cpu in over))
    return host.worker_on_cpu_share(run)
