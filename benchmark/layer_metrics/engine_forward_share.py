"""Share of the engine's time, over every call of the window, spent inside
self.model(...) until it returns: the eager dispatch of every per-op kernel
(phase=forward of decode_engine_phase_seconds over all its phases)."""
NAME = 'engine_forward_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'decode_phases').engine_phase_share(
        run, 'forward')
