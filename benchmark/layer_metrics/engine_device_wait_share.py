"""Share of the engine's time in which the host had enqueued everything and
waited for the device (phase=device_wait of decode_engine_phase_seconds over
all its phases): near zero while the host sets the pace, and what grows once
the device does."""
NAME = 'engine_device_wait_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'decode_phases').engine_phase_share(
        run, 'device_wait')
