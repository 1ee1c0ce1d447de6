"""Share of chip 0's idle seconds in the traced slice that fall under the
leaves in which the worker thread runs its own Python: engine/<call>/pack,
engine/<call>/sample, scheduler/admit, scheduler/emit, scheduler/book. Each
idle gap goes to the leaf span that covers its midpoint, over the leaf list
of lib/host_threads.py, which tiles the worker's cycles; the idle seconds
under no leaf stay in last_run.json (`idle_by_host_leaf`)."""
NAME = 'serve_idle_in_worker_python_share'
LAYER = 'device'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'host_threads').idle_in_worker_python_share(
        run, ctx)
