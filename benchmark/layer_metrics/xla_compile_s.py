"""Seconds inside XLA during set-up: compiling, or reading the persistent
cache (jax.monitoring's backend_compile_duration, lib/compiles.py)."""
NAME = 'xla_compile_s'
LAYER = 'lowering'
UNIT = 's'
MOVES = 'setup_s'
RUNNERS = ('train_step', 'serve_decode')


def read(run, ctx):
    return run['compiles']['setup']['compile_secs']
