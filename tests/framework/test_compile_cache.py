"""Persistent cross-process XLA compilation cache (core/compile_cache.py).

The contract: the cache is placed from OUTSIDE the program. With
JAX_COMPILATION_CACHE_DIR set, jax reads it itself and the package sets no
directory in code; unset, the cache goes to one fixed path derived from the
package's own location; a directory that cannot be created is an error. In
both placements a second COLD process running the same program must
deserialize the compiled executable from disk (jax cache-hit event) instead
of recompiling.

Every case is a cold child process. They are independent, so one module
fixture starts them together (two waves: the first processes, then the
second ones against the directories the first filled) and each test reads
its own children's results."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))

_CHILD = r"""
import json, os
import numpy as np
import jax
import jax.numpy as jnp
from jax import monitoring
events = []
monitoring.register_event_listener(lambda name, **kw: events.append(name))
dir_updates = []
_update = jax.config.update
def spy(name, value):
    if name == 'jax_compilation_cache_dir':
        dir_updates.append(value)
    return _update(name, value)
jax.config.update = spy
# something compiles BEFORE the package configures the cache (eager ops at
# import, scope init): the programs after it must still reach the disk
jax.jit(lambda v: v * 2 + 1)(jnp.ones(3)).block_until_ready()
import paddle_tpu as fluid
from paddle_tpu.core.compile_cache import setup_persistent_cache

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data(name='x', shape=[2, 3], dtype='float32')
    y = fluid.layers.fc(input=x, size=2)
exe = fluid.Executor()   # configures the persistent cache
exe.run(startup)
out = exe.run(main, feed={'x': np.ones((2, 3), np.float32)},
              fetch_list=[y.name])
assert np.isfinite(out[0]).all()
print('CACHE_EVENTS ' + json.dumps({
    'hits': sum(e == '/jax/compilation_cache/cache_hits' for e in events),
    'misses': sum(e == '/jax/compilation_cache/cache_misses' for e in events),
    'dir': setup_persistent_cache(),
    'dir_updates': dir_updates,
}))
"""



_TRAIN_STEP_CHILD = r"""
import json
import numpy as np
from jax import monitoring
events = []
monitoring.register_event_listener(lambda name, **kw: events.append(name))
import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.core.random import seed as set_seed
from paddle_tpu.dygraph.jit import TrainStep
from paddle_tpu.dygraph.nn import Linear
from paddle_tpu.dygraph.tape import dispatch_op


class MLP(dygraph.Layer):
    def __init__(self):
        super().__init__()
        self.a, self.b, self.c = Linear(4, 8), Linear(8, 8), Linear(8, 1)

    def forward(self, x):
        return self.c(self.b(self.a(x)))


def mse(m, x, y):
    d = dispatch_op('elementwise_sub', {'x': m(x), 'y': y}, {})
    return dispatch_op('reduce_mean', {'x': d * d}, {})


with dygraph.guard():
    set_seed(0)
    model = MLP()
    opt = fluid.optimizer.Adam(1e-3, parameter_list=model.parameters())
    step = TrainStep(model, mse, opt)
    before = list(events)
    float(step(np.ones((2, 4), np.float32), np.ones((2, 1), np.float32)))
    mine = events[len(before):]
print('CACHE_EVENTS ' + json.dumps({
    'hits': sum(e == '/jax/compilation_cache/cache_hits' for e in mine),
    'misses': sum(e == '/jax/compilation_cache/cache_misses' for e in mine),
}))
"""

_UNCREATABLE_CHILD = "import paddle_tpu as fluid\nfluid.Executor()\n"

_HATCH_CHILD = (
    "import paddle_tpu as fluid\n"
    "from paddle_tpu.core.compile_cache import setup_persistent_cache\n"
    "assert setup_persistent_cache() is None\n"
    "exe = fluid.Executor()\n"
    "exe.run(fluid.default_startup_program())\n"
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda v: v + 1)(jnp.ones(3)).block_until_ready()\n"
    "print('CACHE_OFF_OK')\n")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PADDLE_TPU_COMPILE_CACHE='1',
               PADDLE_TPU_COMPILE_CACHE_MIN_COMPILE_SECS='0')
    env.pop('JAX_COMPILATION_CACHE_DIR', None)   # conftest's tier-1 dir
    env.update(extra)
    return env


def _start(code, env, cwd=REPO):
    return subprocess.Popen([sys.executable, '-c', code], cwd=cwd, env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def _events(proc):
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    line = next(ln for ln in out.splitlines()
                if ln.startswith('CACHE_EVENTS '))
    return json.loads(line.split(' ', 1)[1])


@pytest.fixture(scope='module')
def children(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('compile_cache')
    # a stand-in checkout: a directory holding a symlink to the package, so
    # the "unset" case never writes CPU executables into the real one
    checkout = tmp / 'checkout'
    checkout.mkdir()
    os.symlink(os.path.join(REPO, 'paddle_tpu'), checkout / 'paddle_tpu')
    blocker = tmp / 'a_file'
    blocker.write_text('not a directory')
    dirs = {'env': tmp / 'xla_cache', 'train': tmp / 'xla_cache_train',
            'off': tmp / 'xla_cache_off', 'checkout': checkout,
            'blocker': blocker}
    placed = _env(JAX_COMPILATION_CACHE_DIR=str(dirs['env']))
    train = _env(JAX_COMPILATION_CACHE_DIR=str(dirs['train']))

    def wave(hashseed):
        return {'env': _start(_CHILD, placed),
                'unset': _start(_CHILD, _env(), cwd=str(checkout)),
                'train': _start(_TRAIN_STEP_CHILD,
                                dict(train, PYTHONHASHSEED=hashseed))}

    first = wave('1')
    uncreatable = _start(_UNCREATABLE_CHILD, _env(
        JAX_COMPILATION_CACHE_DIR=str(blocker / 'sub')))
    hatch = _start(_HATCH_CHILD, _env(
        PADDLE_TPU_COMPILE_CACHE='0',
        JAX_COMPILATION_CACHE_DIR=str(dirs['off'])))
    first = {k: _events(p) for k, p in first.items()}
    second = wave('2')
    return {'dirs': dirs, 'first': first,
            'second': {k: _events(p) for k, p in second.items()},
            'uncreatable': (uncreatable,) + uncreatable.communicate(
                timeout=600),
            'hatch': (hatch,) + hatch.communicate(timeout=600)}


def test_env_placed_cache_second_cold_process_hits(children):
    """JAX_COMPILATION_CACHE_DIR places the cache: the package writes there,
    never calls config.update on the directory, and a second cold process
    hits."""
    cache_dir = children['dirs']['env']
    first, second = children['first']['env'], children['second']['env']
    assert first['dir'] == str(cache_dir)
    assert first['dir_updates'] == [], first
    assert first['misses'] > 0 and first['hits'] == 0, first
    assert os.listdir(cache_dir), \
        "first process must persist compiled executables"
    assert second['hits'] > 0, second
    assert second['misses'] == 0, \
        f"second cold process recompiled despite the disk cache: {second}"


def test_unset_env_uses_fixed_path_beside_the_package(children):
    """No JAX_COMPILATION_CACHE_DIR: the cache is at <checkout>/.xla_cache,
    derived from the package's own location (shown on the stand-in
    checkout), and the fixed path is what makes the second process hit."""
    want = str(children['dirs']['checkout'] / '.xla_cache')
    first, second = children['first']['unset'], children['second']['unset']
    assert first['dir'] == want, first
    assert first['dir_updates'] == [want], first
    assert first['misses'] > 0, first
    assert os.listdir(want)
    assert second['dir'] == want
    assert second['hits'] > 0 and second['misses'] == 0, second


def test_fixed_path_is_inside_the_checkout_and_ignored():
    from paddle_tpu.core.compile_cache import DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, '.xla_cache')
    with open(os.path.join(REPO, '.gitignore')) as f:
        assert '.xla_cache/' in f.read().split()


def test_uncreatable_cache_dir_is_an_error(children):
    """A cache directory that cannot be created used to disable the cache
    silently (every process compiles cold, nobody is told)."""
    proc, _, err = children['uncreatable']
    assert proc.returncode != 0
    assert 'cannot be created' in err \
        and str(children['dirs']['blocker']) in err, err[-2000:]


def test_env_hatch_disables_cache(children):
    proc, out, err = children['hatch']
    assert proc.returncode == 0, err[-3000:]
    assert 'CACHE_OFF_OK' in out
    assert not children['dirs']['off'].exists()


def test_train_step_hits_in_a_second_process(children):
    """The fused TrainStep must lower the SAME program in every process:
    its update ops used to be traced in the iteration order of a set of
    parameter names, which string-hash randomisation (PYTHONHASHSEED, 1 and
    2 here) changes per process, so the step never hit the persistent cache
    (found on the chip: the ResNet-50 step recompiled for 50 s beside its
    own cached executable)."""
    first, second = children['first']['train'], children['second']['train']
    assert first['misses'] > 0 and first['hits'] == 0, (first, second)
    assert second['hits'] > 0 and second['misses'] == 0, (first, second)


def test_program_locations_do_not_move_with_the_call_stack():
    """A pallas kernel's cache key holds its Mosaic body with every MLIR
    location in it, so a location that is the whole Python traceback (jax's
    default) makes the key follow the caller: found on the chip, where the
    decode engine's prefill programs with the flash kernel missed their own
    cached executables in every process that ran with telemetry on
    (tape.dispatch_op calls through another line there). With the cache set
    up a location is the innermost frame alone: the same function lowered
    from two call sites gives the same text, debug info included."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.compile_cache import setup_persistent_cache
    assert setup_persistent_cache()
    assert not jax.config.jax_include_full_tracebacks_in_locations

    def lowered():
        # a fresh function each time: jit would hand a second lowering of
        # the same one the first one's trace, locations and all
        return jax.jit(lambda v: jnp.sin(v) * 2).lower(jnp.ones(3))

    def one_call_site():
        return lowered().as_text(debug_info=True)

    def another_call_site():
        text = lowered().as_text(debug_info=True)
        return text

    text = one_call_site()
    assert 'test_compile_cache.py' in text       # locations are still there
    assert text == another_call_site()
