"""TrainStep fused-step tests: basic SGD parity and gradient merge
(accum_steps, ref GradientMergeOptimizer semantics — optimizer.py:3870)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.dygraph.jit import TrainStep
from paddle_tpu.dygraph.nn import Linear
from paddle_tpu.dygraph.tape import dispatch_op


def _mse(m, x, y):
    d = dispatch_op('elementwise_sub', {'x': m(x), 'y': y}, {})
    sq = dispatch_op('elementwise_mul', {'x': d, 'y': d}, {})
    return dispatch_op('reduce_mean', {'x': sq}, {})


def _make(seed=0):
    from paddle_tpu.core.random import seed as set_seed
    set_seed(seed)  # param init draws from the framework PRNG stream
    model = Linear(4, 1)
    opt = fluid.optimizer.SGD(0.1, parameter_list=model.parameters())
    return model, opt


def test_train_step_matches_manual_sgd():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)
    with dygraph.guard():
        model, opt = _make()
        w0 = {n: np.asarray(p.value).copy()
              for n, p in model.named_parameters()}
        step = TrainStep(model, _mse, opt)
        step(x, y)
        got = {n: np.asarray(p.value) for n, p in model.named_parameters()}

    # manual: w -= lr * dL/dw for the same MSE
    w, b = w0['weight'], w0['bias']
    pred = x @ w + b
    d = (pred - y)
    gw = 2.0 * x.T @ d / d.size
    gb = 2.0 * d.sum(axis=0) / d.size
    np.testing.assert_allclose(got['weight'], w - 0.1 * gw, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got['bias'], b - 0.1 * gb, rtol=1e-5,
                               atol=1e-6)


def test_grad_merge_applies_every_k_steps():
    rng = np.random.RandomState(1)
    batches = [(rng.randn(4, 4).astype(np.float32),
                rng.randn(4, 1).astype(np.float32)) for _ in range(4)]
    with dygraph.guard():
        model, opt = _make(seed=1)
        w0 = {n: np.asarray(p.value).copy()
              for n, p in model.named_parameters()}
        step = TrainStep(model, _mse, opt, accum_steps=4)
        for i, (x, y) in enumerate(batches):
            step(x, y)
            got = {n: np.asarray(p.value)
                   for n, p in model.named_parameters()}
            if i < 3:  # params must NOT move before the k-th call
                for n in w0:
                    np.testing.assert_array_equal(got[n], w0[n])
    # after k calls: one SGD update with the MEAN of the k grads
    mean_gw = np.zeros_like(w0['weight'])
    mean_gb = np.zeros_like(w0['bias'])
    for x, y in batches:
        d = x @ w0['weight'] + w0['bias'] - y
        mean_gw += 2.0 * x.T @ d / d.size
        mean_gb += 2.0 * d.sum(axis=0) / d.size
    mean_gw /= 4.0
    mean_gb /= 4.0
    np.testing.assert_allclose(got['weight'], w0['weight'] - 0.1 * mean_gw,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got['bias'], w0['bias'] - 0.1 * mean_gb,
                               rtol=1e-5, atol=1e-6)


def test_grad_merge_two_cycles():
    """Second merge cycle starts from a zeroed accumulator."""
    rng = np.random.RandomState(2)
    x = rng.randn(4, 4).astype(np.float32)
    y = rng.randn(4, 1).astype(np.float32)
    with dygraph.guard():
        model, opt = _make(seed=2)
        step = TrainStep(model, _mse, opt, accum_steps=2)
        for _ in range(4):
            step(x, y)
        merged = {n: np.asarray(p.value)
                  for n, p in model.named_parameters()}
    with dygraph.guard():
        model2, opt2 = _make(seed=2)
        plain = TrainStep(model2, _mse, opt2)
        for _ in range(2):  # same data k times → mean grad == plain grad
            plain(x, y)
        expect = {n: np.asarray(p.value)
                  for n, p in model2.named_parameters()}
    for n in merged:
        np.testing.assert_allclose(merged[n], expect[n], rtol=1e-5,
                                   atol=1e-6)


def test_data_sharded_step_places_state_and_compiles_once():
    """GSPMD path (TrainStep(data_sharding=...), the fleet dp entry point):
    parameters and optimizer slots start on one device; the step must put
    them on the batch's mesh BEFORE its first dispatch, or step 1 compiles
    for single-device state and step 2 compiles the whole program again for
    the replicated state step 1 handed back."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({'dp': 4}, jax.devices()[:4])
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)

    def run(sharding, accum_steps=1):
        with dygraph.guard():
            from paddle_tpu.core.random import seed as set_seed
            set_seed(0)
            model = Linear(4, 1)
            opt = fluid.optimizer.Adam(0.1,
                                       parameter_list=model.parameters())
            step = TrainStep(model, _mse, opt, data_sharding=sharding,
                             accum_steps=accum_steps)
            losses = [float(step(x, y)) for _ in range(3)]
            state = [p.value for p in model.parameters()] + [
                v for slots in step._slots.values() for v in slots.values()]
        return step, losses, state

    step, losses, state = run(NamedSharding(mesh, P('dp')))
    assert step._jitted._cache_size() == 1
    assert all(set(a.devices()) == set(mesh.devices.flat) for a in state)
    _, want, _ = run(None)
    np.testing.assert_allclose(losses, want, rtol=1e-5)
    # gradient merge adds accumulators and a step counter to the state
    assert run(NamedSharding(mesh, P('dp')), 2)[0]._jitted._cache_size() == 1
