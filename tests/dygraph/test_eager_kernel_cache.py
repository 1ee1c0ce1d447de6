"""Eager per-op jitted-kernel cache (dygraph/tape.py): hit/miss accounting,
LRU bound, cache-on/off numerical identity (seed-pinned, incl. RNG ops),
attr-hashability bypass, and the PADDLE_TPU_EAGER_CACHE env hatch."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.dygraph.tape import (_attr_sig, _Unhashable, dispatch_op,
                                     kernel_cache)
from paddle_tpu.dygraph.nn import Linear

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))


@pytest.fixture(autouse=True)
def _fresh_cache():
    old_enabled, old_max = kernel_cache.enabled, kernel_cache.maxsize
    kernel_cache.clear()
    kernel_cache.enabled = True
    yield
    kernel_cache.clear()
    kernel_cache.enabled, kernel_cache.maxsize = old_enabled, old_max


def _train_trace(seed):
    """One seed-pinned fwd+bwd micro-trace; returns (loss, grads, dropout)."""
    from paddle_tpu.core.random import seed as set_seed
    set_seed(seed)
    model = Linear(4, 3)
    x = dygraph.to_variable(
        np.random.RandomState(seed).randn(8, 4).astype(np.float32))
    y = model(x)
    d = dispatch_op('dropout', {'x': y}, {'dropout_prob': 0.5})
    loss = dispatch_op('reduce_mean', {'x': d * d}, {})
    loss.backward()
    return (float(loss.value),
            {n: np.asarray(p.grad) for n, p in model.named_parameters()},
            np.asarray(d.value))


def test_cache_numerics_identical_on_off():
    with dygraph.guard():
        with dygraph.eager_kernel_cache_guard(False):
            l0, g0, d0 = _train_trace(7)
            assert kernel_cache.stats()['hits'] == 0
        with dygraph.eager_kernel_cache_guard(True):
            l1, g1, d1 = _train_trace(7)
            assert kernel_cache.stats()['misses'] > 0
            # second identical trace: every dispatch is a hit
            before = kernel_cache.stats()['misses']
            l2, g2, d2 = _train_trace(7)
            assert kernel_cache.stats()['misses'] == before
            assert kernel_cache.stats()['hits'] > 0
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    np.testing.assert_array_equal(d0, d1)   # same PRNG stream either way
    np.testing.assert_array_equal(d1, d2)
    for n in g0:
        np.testing.assert_allclose(g0[n], g1[n], rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(g1[n], g2[n], rtol=1e-6, atol=1e-7)


def test_repeat_dispatch_hits_cache():
    with dygraph.guard():
        t = dygraph.to_variable(np.ones((3, 3), np.float32))
        for _ in range(5):
            dispatch_op('scale', {'x': t}, {'scale': 2.0})
    s = kernel_cache.stats()
    assert s['misses'] == 1 and s['hits'] == 4


def _resnet_block():
    from paddle_tpu.models.resnet import BottleneckBlock
    return BottleneckBlock(64, 16, stride=1, shortcut=True), (2, 64, 8, 8)


def _bert_layer():
    from paddle_tpu.models.bert import BertConfig, TransformerLayer
    cfg = BertConfig(hidden_size=64, num_attention_heads=2,
                     intermediate_size=128, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    return TransformerLayer(cfg), (1, 8, 64)


@pytest.mark.parametrize('make_block', [_resnet_block, _bert_layer])
def test_repeated_train_step_of_a_model_block_hits_cache(make_block):
    """A whole eager training step (forward, tape backward, SGD update) of
    a ResNet bottleneck and of a BERT transformer layer: the first step
    fills the cache, and every dispatch of the steps after it is a hit."""
    with dygraph.guard():
        model, shape = make_block()
        opt = fluid.optimizer.SGD(0.01, parameter_list=model.parameters())
        x = dygraph.to_variable(
            np.random.RandomState(0).randn(*shape).astype(np.float32))

        def step():
            out = model(x)
            loss = dispatch_op('reduce_mean', {'x': out * out}, {})
            loss.backward()
            opt.minimize(loss)
            opt.clear_gradients()
            return float(loss.value)

        first = step()
        filled = kernel_cache.stats()
        assert filled['misses'] > 0
        later = [step(), step()]
        s = kernel_cache.stats()
    assert s['misses'] == filled['misses'], (filled, s)
    assert s['hits'] > filled['hits']
    assert np.isfinite([first] + later).all()


def test_distinct_shapes_and_attrs_miss():
    with dygraph.guard():
        a = dygraph.to_variable(np.ones((2, 2), np.float32))
        b = dygraph.to_variable(np.ones((4, 2), np.float32))
        dispatch_op('scale', {'x': a}, {'scale': 2.0})
        dispatch_op('scale', {'x': b}, {'scale': 2.0})   # new shape
        dispatch_op('scale', {'x': b}, {'scale': 3.0})   # new attr
    assert kernel_cache.stats()['misses'] == 3


def test_lru_bound_evicts():
    dygraph.set_eager_kernel_cache(True, maxsize=2)
    with dygraph.guard():
        t = dygraph.to_variable(np.ones((2, 2), np.float32))
        for s in (1.0, 2.0, 3.0, 4.0):
            dispatch_op('scale', {'x': t}, {'scale': s})
    st = kernel_cache.stats()
    assert st['size'] <= 2 and st['evictions'] == 2


def test_unhashable_attr_bypasses_not_breaks():
    assert _attr_sig({'a': [1, (2, 'x')], 'b': None}) is not None
    with pytest.raises(_Unhashable):
        _attr_sig(np.zeros(3))
    with dygraph.guard():
        t = dygraph.to_variable(np.ones((2,), np.float32))
        out = dispatch_op('scale', {'x': t}, {'scale': np.asarray(2.0)})
        np.testing.assert_allclose(np.asarray(out.value), [2.0, 2.0])
    assert kernel_cache.stats()['bypasses'] >= 1


def test_backward_through_cached_kernels_twice_raises():
    """retain_graph semantics survive the cached path: the freed-graph
    error must still fire on a second backward()."""
    with dygraph.guard():
        model = Linear(3, 1)
        x = dygraph.to_variable(np.ones((2, 3), np.float32))
        loss = dispatch_op('reduce_mean', {'x': model(x)}, {})
        loss.backward()
        with pytest.raises(RuntimeError, match='freed'):
            loss.backward()


def test_env_escape_hatch_disables_cache():
    code = (
        "import numpy as np\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu import dygraph\n"
        "from paddle_tpu.dygraph.tape import dispatch_op, kernel_cache\n"
        "with dygraph.guard():\n"
        "    t = dygraph.to_variable(np.ones((2, 2), np.float32))\n"
        "    for _ in range(3):\n"
        "        dispatch_op('scale', {'x': t}, {'scale': 2.0})\n"
        "s = kernel_cache.stats()\n"
        "assert not s['enabled'] and s['size'] == 0 and s['hits'] == 0, s\n"
        "print('HATCH_OK')\n")
    env = dict(os.environ, PADDLE_TPU_EAGER_CACHE='0', JAX_PLATFORMS='cpu')
    r = subprocess.run([sys.executable, '-c', code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'HATCH_OK' in r.stdout
