"""Share of the wall seconds of the engine's forward phase (the dispatch of
a call's one program), over every call of the window, in which the worker
thread did not run: wall - CPU of phase=forward, the CPU from
decode_engine_phase_cpu_seconds. Lock wait and the runtime's own blocking;
beside engine_forward_share it says what a dispatch is made of
(lib/host_threads.py)."""
NAME = 'engine_forward_offcpu_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'host_threads').engine_forward_offcpu_share(run)
