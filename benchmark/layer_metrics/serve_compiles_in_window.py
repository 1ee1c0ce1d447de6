"""Executables XLA built or loaded inside the measured window (jax.monitoring,
lib/compiles.py). Must read 0: a compile in the window stalls it, and the
run is then not correct."""
NAME = 'serve_compiles_in_window'
LAYER = 'lowering'
UNIT = 'count'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return ctx.module('lib', 'readers').compiles_in_window(run)
