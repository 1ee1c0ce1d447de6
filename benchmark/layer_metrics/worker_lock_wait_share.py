"""Share of the scheduler worker's busy wall seconds (cycle - wait) that it
spent OFF the CPU in the phases that never block of themselves: wall - CPU
summed over pack and sample of every engine call, admit, emit and book. In
pure interpreter and numpy work, off the CPU is waiting for the interpreter
lock. A lower bound: waits inside forward are not in it
(lib/host_threads.py)."""
NAME = 'worker_lock_wait_share'
LAYER = 'scheduler'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'host_threads').worker_lock_wait_share(run)
