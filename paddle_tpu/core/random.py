"""PRNG key plumbing.

TPU-first determinism story: one global seed → jax PRNG key tree. Static-graph
lowering folds (step_counter, op_index) into the base key so every random op
gets a distinct, reproducible stream; dygraph and initializers draw from a
global splitting generator. Replaces the reference's per-op `seed` attrs and
cuRAND states (ref: paddle/fluid/operators/dropout_op.cu seed handling).
"""
from __future__ import annotations

import contextlib

import jax


class KeyGenerator:
    """LAZY: building the PRNGKey initializes the jax backend, so it must
    not happen at construction — `import paddle_tpu` has to stay free of
    backend init."""

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._base = None
        self._counter = 0

    def seed(self, seed: int):
        self._seed = int(seed)
        self._base = None
        self._counter = 0

    @property
    def _key(self):
        if self._base is None:
            self._base = jax.random.PRNGKey(self._seed)
        return self._base

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self._key, self._counter)

    def state(self):
        """Resumable generator state (resilience checkpoints): the stream
        is fully determined by (seed, counter)."""
        return {'seed': self._seed, 'counter': self._counter}

    def set_state(self, state):
        """Restore a :meth:`state` snapshot — the next `next_key()` draws
        exactly what the captured process would have drawn."""
        self._seed = int(state['seed'])
        self._base = None            # lazily rebuilt from the seed
        self._counter = int(state['counter'])

    def base_key(self):
        return self._key

    @contextlib.contextmanager
    def bind_base(self, base_key):
        """Derive keys from `base_key` (possibly a jit tracer) inside the
        context. Used by `to_static` tracing so random ops fold counters into
        a per-call key argument instead of baking a host constant into the
        compiled program (which would freeze dropout masks across calls)."""
        old = self._base, self._counter
        self._base = base_key
        self._counter = 0
        try:
            yield
        finally:
            self._base, self._counter = old


default_generator = KeyGenerator(0)


def seed(s: int):
    """Global seed entry point (ref: fluid.default_main_program().random_seed)."""
    from .. import framework
    framework.manual_seed(s)
    default_generator.seed(s)
    return default_generator
