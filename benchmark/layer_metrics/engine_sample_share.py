"""Share of the engine's time spent choosing tokens on the host: the step's
argmax over every slot's row, the prefill's argmax or sampler (phase=sample
of decode_engine_phase_seconds over all its phases)."""
NAME = 'engine_sample_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'decode_phases').engine_phase_share(
        run, 'sample')
