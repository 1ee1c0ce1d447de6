"""Executables XLA built or loaded inside the measured window (jax.monitoring,
lib/compiles.py). Must read 0: a compile in the window stalls it, and the
run is then not correct."""
NAME = 'train_compiles_in_window'
LAYER = 'lowering'
UNIT = 'count'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    return ctx.module('lib', 'readers').compiles_in_window(run)
