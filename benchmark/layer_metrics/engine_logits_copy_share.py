"""Share of the engine's time spent copying logits from the device to the
host (phase=logits_copy of decode_engine_phase_seconds over all its
phases)."""
NAME = 'engine_logits_copy_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'decode_phases').engine_phase_share(
        run, 'logits_copy')
