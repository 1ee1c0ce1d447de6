"""Program-level IR pass pipeline (paddle_tpu/ir/): numerics parity,
idempotence, eqn-count accounting, per-pass safety rules, metrics export,
and compile-cache keying.

Parity contract: pass-on and pass-off runs of the SAME program from the
SAME initial state produce bit-identical fetches — including through
dropout, because every surviving op keeps its pre-rewrite RNG salt
(ir/pass_base.stamp_rng_salts + executor.run_seq)."""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import ir, layers as L
from paddle_tpu.compiler import BuildStrategy, CompiledProgram

from shared_programs import (build_bert_layer, build_mlp_adam,
                             build_resnet_block)


def _fused_bs():
    bs = BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    bs.fuse_all_optimizer_ops = True
    return bs


def _snapshot(program):
    scope = fluid.global_scope()
    return {v.name: np.asarray(scope.find(v.name))
            for v in program.list_vars()
            if v.persistable and scope.find(v.name) is not None}


def _restore(snap):
    scope = fluid.global_scope()
    for k, v in snap.items():
        scope.set(k, v)


def _run_steps(program, feed, fetches, snap, passes_on, steps=3,
               build_strategy=None, seed=0):
    """Fresh Executor + restored state + reseeded RNG per mode: the ONLY
    difference between modes is the pass pipeline."""
    from paddle_tpu.core.random import seed as set_seed
    _restore(snap)
    set_seed(seed)
    old = os.environ.get('PADDLE_TPU_PASSES')
    os.environ['PADDLE_TPU_PASSES'] = '1' if passes_on else '0'
    try:
        exe = fluid.Executor()
        target = CompiledProgram(program,
                                 build_strategy=build_strategy or _fused_bs())
        outs = []
        for _ in range(steps):
            outs.append([np.asarray(o) for o in
                         exe.run(target, feed=feed, fetch_list=fetches)])
        return outs
    finally:
        if old is None:
            os.environ.pop('PADDLE_TPU_PASSES', None)
        else:
            os.environ['PADDLE_TPU_PASSES'] = old


def _assert_parity(program, feed, fetches, snap, **kw):
    a = _run_steps(program, feed, fetches, snap, False, **kw)
    b = _run_steps(program, feed, fetches, snap, True, **kw)
    for step_i, (xs, ys) in enumerate(zip(a, b)):
        for x, y in zip(xs, ys):
            np.testing.assert_array_equal(
                x, y, err_msg=f'pass-on/off diverged at step {step_i}')


# ---------------------------------------------------------------------------
# parity: the three ISSUE models
# ---------------------------------------------------------------------------

def _build_mnist_mlp():
    """MNIST-recipe MLP: two relu fc hiddens + softmax cross entropy, Adam
    (ref examples: recognize_digits). Sized down for tier-1 wall time."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = L.data('img', [64], dtype='float32')
        label = L.data('label', [1], dtype='int64')
        h = L.fc(img, size=32, act='relu')
        h = L.fc(h, size=32, act='relu')
        logits = L.fc(h, size=10)
        loss = L.reduce_mean(
            L.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'img': rng.randn(8, 64).astype(np.float32),
            'label': rng.randint(0, 10, (8, 1)).astype(np.int64)}
    return main, startup, feed, loss


def test_parity_mnist_mlp():
    main, startup, feed, loss = _build_mnist_mlp()
    fluid.Executor().run(startup)
    _assert_parity(main, feed, [loss], _snapshot(main))


def test_parity_resnet_bottleneck_block():
    main, startup, make_feed, loss = build_resnet_block()
    fluid.Executor().run(startup)
    _assert_parity(main, make_feed(), [loss], _snapshot(main))


def test_parity_bert_layer():
    main, startup, make_feed, loss = build_bert_layer()
    fluid.Executor().run(startup)
    _assert_parity(main, make_feed(), [loss], _snapshot(main))


def test_parity_through_dropout_with_dce():
    """The RNG-salt stamp: DCE removes a dead op BEFORE the dropout, which
    would shift the dropout's fold_in index — parity must survive because
    surviving ops keep their pre-rewrite salt."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [16], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        L.scale(x, scale=3.0)                  # dead: output never used
        h = L.fc(x, size=16, act='relu')
        h = L.dropout(h, dropout_prob=0.5)
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    fluid.Executor().run(startup)
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name])
    assert ctx.stats['dce']['removed_ops'] >= 1
    rng = np.random.RandomState(1)
    feed = {'x': rng.randn(8, 16).astype(np.float32),
            'y': rng.randn(8, 1).astype(np.float32)}
    _assert_parity(main, feed, [loss], _snapshot(main))


# ---------------------------------------------------------------------------
# idempotence & eqn-count guarantees
# ---------------------------------------------------------------------------

def _op_tuples(program):
    return [(op.type, {k: list(v) for k, v in op.inputs.items()},
             {k: list(v) for k, v in op.outputs.items()},
             {k: repr(v) for k, v in op.attrs.items()})
            for op in program.global_block().ops]


def test_pipeline_idempotent():
    main, startup, make_feed, loss = build_mlp_adam()
    once, _ = ir.apply_pipeline(main, fetch_names=[loss.name],
                                build_strategy=_fused_bs())
    twice, ctx2 = ir.apply_pipeline(once, fetch_names=[loss.name],
                                    build_strategy=_fused_bs())
    assert _op_tuples(once) == _op_tuples(twice)
    assert ctx2.stats['dce'] == {'removed_ops': 0, 'removed_vars': 0}


def _count_eqns(jaxpr):
    """Total equations including nested (pjit/cond/scan/remat) jaxprs."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                total += _count_eqns(sub)
    return total


def _sub_jaxprs(v):
    from jax.extend import core as jex_core
    if isinstance(v, jex_core.Jaxpr):
        return [v]
    if isinstance(v, jex_core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, (list, tuple)):
        return [s for x in v for s in _sub_jaxprs(x)]
    return []


def _eqn_count(program, feed, fetches):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.executor import _lower
    scope = fluid.global_scope()
    state = {v.name: jnp.asarray(scope.find(v.name))
             for v in program.list_vars() if v.persistable}
    feed_vals = {k: jnp.asarray(v) for k, v in feed.items()}
    step = _lower(program, sorted(feed_vals), fetches, sorted(state))
    j = jax.make_jaxpr(step)({}, state, feed_vals, jax.random.PRNGKey(0))
    return _count_eqns(j.jaxpr)


def test_fused_optimizer_and_dce_strictly_shrink_adam_program():
    main, startup, make_feed, loss = build_mlp_adam()
    fluid.Executor().run(startup)
    feed = make_feed()
    base = _eqn_count(main, feed, [loss.name])
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name],
                                 build_strategy=_fused_bs())
    assert ctx.stats['fuse_all_optimizer_ops']['fused_groups'] >= 1
    fused = _eqn_count(opt, feed, [loss.name])
    assert fused < base, (base, fused)
    # the multi-param Adam acceptance margin
    assert 1 - fused / base >= 0.30, (base, fused)
    assert len(opt.global_block().ops) < len(main.global_block().ops)


@pytest.mark.parametrize('builder', [build_mlp_adam, build_resnet_block,
                                     build_bert_layer],
                         ids=lambda b: b.__name__[len('build_'):])
def test_pipeline_strictly_shrinks_every_recipe_op_list(builder):
    """With the fuse knobs live the pipeline hands the tracer strictly
    fewer global-block ops on all three training recipes."""
    main, _startup, _make_feed, loss = builder()
    opt, _ = ir.apply_pipeline(main, fetch_names=[loss.name],
                               build_strategy=_fused_bs())
    assert len(opt.global_block().ops) < len(main.global_block().ops)


def test_dce_removes_dead_ops_and_vars():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = L.data('x', [4], dtype='float32')
        live = L.scale(x, scale=2.0)
        d1 = L.scale(x, scale=5.0)             # dead chain root
        L.elementwise_add(d1, d1)              # dead consumer
    opt, ctx = ir.apply_pipeline(main, fetch_names=[live.name])
    assert ctx.stats['dce']['removed_ops'] == 2
    assert [op.type for op in opt.global_block().ops] == ['scale']
    assert not opt.global_block().has_var(d1.name)
    # original program untouched
    assert len(main.global_block().ops) == 3


def test_dce_keeps_persistable_writes_and_fetches():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [4], dtype='float32')
        acc = fluid.layers.tensor.create_global_var(
            [4], 0.0, 'float32', persistable=True, name='acc_var')
        # write to persistable state: never dead, even though nothing
        # downstream reads it
        main.global_block().append_op(
            'elementwise_add', inputs={'x': acc.name, 'y': x.name},
            outputs={'Out': acc.name}, attrs={})
        out = L.scale(x, scale=2.0)
    opt, _ = ir.apply_pipeline(main, fetch_names=[out.name])
    assert [op.type for op in opt.global_block().ops] == \
        ['elementwise_add', 'scale']


# ---------------------------------------------------------------------------
# constant folding
# ---------------------------------------------------------------------------

def test_constant_folding_collapses_fill_scale_cast_chain():
    from paddle_tpu.layers import tensor as T
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = L.data('x', [4], dtype='float32')
        c = T.fill_constant([4], 'float32', 2.0)
        s = L.scale(c, scale=3.0, bias=1.0)          # → 7.0
        cst = L.cast(s, 'float32')
        y = L.elementwise_add(x, cst)
    opt, ctx = ir.apply_pipeline(main, fetch_names=[y.name])
    assert ctx.stats['constant_fold']['folded_ops'] == 2
    kinds = [op.type for op in opt.global_block().ops]
    assert kinds == ['fill_constant', 'elementwise_add']
    assert float(opt.global_block().ops[0].attrs['value']) == 7.0
    xv = np.ones((2, 4), np.float32)
    out, = fluid.Executor().run(main, feed={'x': xv}, fetch_list=[y])
    np.testing.assert_array_equal(out, xv + 7.0)


def test_constant_folding_respects_reassignment():
    """A var rewritten by a non-constant op between producer and consumer
    must not fold (current-value dataflow)."""
    from paddle_tpu.framework import Operator
    from paddle_tpu.layers import tensor as T
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = L.data('x', [4], dtype='float32')
        c = T.fill_constant([4], 'float32', 2.0)
        blk = main.global_block()
        # overwrite c with a runtime value, THEN scale it
        blk.append_op('elementwise_add', inputs={'x': c.name, 'y': x.name},
                      outputs={'Out': c.name}, attrs={})
        y = L.scale(c, scale=3.0)
    opt, ctx = ir.apply_pipeline(main, fetch_names=[y.name])
    assert ctx.stats['constant_fold']['folded_ops'] == 0
    assert [op.type for op in opt.global_block().ops] == \
        ['fill_constant', 'elementwise_add', 'scale']


# ---------------------------------------------------------------------------
# fuse_elewise_add_act safety
# ---------------------------------------------------------------------------

def _add_relu_program(fetch_mid=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        h = L.fc(x, size=8, act='relu')       # mul + add + relu
        out = L.reduce_sum(h)
    return main, startup, h, out


def test_fuse_add_act_fuses_fc_bias_relu():
    main, _, _, out = _add_relu_program()
    bs = BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    opt, ctx = ir.apply_pipeline(main, fetch_names=[out.name],
                                 build_strategy=bs)
    kinds = [op.type for op in opt.global_block().ops]
    assert 'fused_elemwise_add_activation' in kinds
    assert 'relu' not in kinds and 'elementwise_add' not in kinds
    assert ctx.stats['fuse_elewise_add_act']['fused_pairs'] == 1


def test_fuse_add_act_skips_fetched_intermediate():
    """The add's output is observable (fetched) → must not be fused away."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        y = L.data('y', [8], dtype='float32')
        mid = L.elementwise_add(x, y)
        out = L.relu(mid)
    bs = BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    opt, ctx = ir.apply_pipeline(main, fetch_names=[out.name, mid.name],
                                 build_strategy=bs)
    kinds = [op.type for op in opt.global_block().ops]
    assert 'fused_elemwise_add_activation' not in kinds


def test_fuse_add_act_requires_flag():
    main, _, _, out = _add_relu_program()
    opt, _ = ir.apply_pipeline(main, fetch_names=[out.name])  # default bs
    assert 'fused_elemwise_add_activation' not in \
        [op.type for op in opt.global_block().ops]


# ---------------------------------------------------------------------------
# fuse_all_optimizer_ops safety
# ---------------------------------------------------------------------------

def test_fuse_optimizer_groups_by_hyperparameters():
    """Two Adam families with different betas must not merge into one
    bundle (their updates are not interchangeable)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=8)
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        opt1 = fluid.optimizer.Adam(learning_rate=1e-3, beta1=0.9)
        opt2 = fluid.optimizer.Adam(learning_rate=1e-3, beta1=0.8)
        params = main.all_parameters()
        grads = opt1.backward(loss)
        half = len(grads) // 2
        opt1.apply_gradients(grads[:half])
        opt2.apply_gradients(grads[half:])
    bs = BuildStrategy()
    bs.fuse_all_optimizer_ops = True
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name],
                                 build_strategy=bs)
    fused = [op for op in opt.global_block().ops
             if op.type == 'fused_adam']
    assert len(fused) == 2
    betas = sorted(op.attrs['beta1'] for op in fused)
    assert betas == [0.8, 0.9]


def test_fused_state_roundtrips_through_scope():
    """Slots updated through the fused op land back in the scope under
    their per-param names (checkpoint/save_persistables compatibility)."""
    main, startup, make_feed, loss = build_mlp_adam(layers_n=2)
    fluid.Executor().run(startup)
    snap = _snapshot(main)
    _run_steps(main, make_feed(), [loss], snap, True, steps=2)
    scope = fluid.global_scope()
    pow_names = [n for n in snap if 'beta1_pow' in n]
    assert pow_names
    for n in pow_names:
        # two fused steps: beta1_pow advanced from 0.9 to 0.9^3
        np.testing.assert_allclose(np.asarray(scope.find(n)),
                                   np.asarray([0.9 ** 3]), rtol=1e-6)


# ---------------------------------------------------------------------------
# wiring: env escape hatch, cache keying, metrics
# ---------------------------------------------------------------------------

def test_env_escape_hatch_disables_pipeline(monkeypatch):
    main, _, _, out = _add_relu_program()
    monkeypatch.setenv('PADDLE_TPU_PASSES', '0')
    opt, ctx = ir.apply_pipeline(main, fetch_names=[out.name],
                                 build_strategy=_fused_bs())
    assert opt is main            # untouched, not even cloned
    assert ctx.stats == {}
    assert ir.pipeline_signature(_fused_bs()) == ()


def test_env_selects_explicit_pass_list(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PASSES', 'dce,constant_fold')
    assert ir.build_pipeline().names() == ('constant_fold', 'dce')
    assert ir.pipeline_signature(None) == ('dce', 'constant_fold')


def test_pass_signature_keys_the_executor_cache():
    main, startup, feed, loss = _build_mnist_mlp()
    exe = fluid.Executor()
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    assert len(exe._cache) == 1
    bs = _fused_bs()
    exe.run(CompiledProgram(main, build_strategy=bs), feed=feed,
            fetch_list=[loss])
    # fuse flags changed the pipeline signature → fresh lowering
    assert len(exe._cache) == 2


def test_ir_pass_metrics_exported():
    from paddle_tpu import observability as obs
    main, startup, make_feed, loss = build_mlp_adam(layers_n=2)
    fluid.Executor().run(startup)
    with obs.telemetry_guard(True):
        obs.reset()
        exe = fluid.Executor()
        exe.run(CompiledProgram(main, build_strategy=_fused_bs()),
                feed=make_feed(), fetch_list=[loss])
        metrics = obs.registry.to_dict()
    assert 'ir_pass_applied_total' in metrics
    applied = {s['labels']['pass'] for s in
               metrics['ir_pass_applied_total']['samples']}
    assert {'constant_fold', 'fuse_elewise_add_act',
            'fuse_all_optimizer_ops', 'dce'} <= applied
    assert 'ir_pass_seconds' in metrics
    assert 'ir_pass_pipeline_runs' in metrics
