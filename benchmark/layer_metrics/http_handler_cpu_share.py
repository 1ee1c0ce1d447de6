"""Thread-CPU seconds of the HTTP handler threads (http_handler_cpu_seconds,
one increment a request, entry to last byte written) over the seconds the
registry covers, in percent of one core: at most that share of the one
interpreter went to the handlers' side of the window's requests (socket calls
run without the lock: an upper bound on the lock held;
lib/host_threads.py)."""
NAME = 'http_handler_cpu_share'
LAYER = 'scheduler_entry'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'host_threads').http_handler_cpu_share(run)
