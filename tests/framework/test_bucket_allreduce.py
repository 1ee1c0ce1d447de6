"""bucket_allreduce IR pass (ir/bucket_allreduce.py): fleet's per-grad
c_allreduce_sum insertion, size-capped bucket formation, the live
fuse_all_reduce_ops knobs (BuildStrategy AND DistributedStrategy), strict
env parsing, and — the acceptance — BITWISE pass-on/off parity on the
MNIST-MLP and ResNet-block recipes at comm_dtype=f32.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import ir, layers
from paddle_tpu import observability as obs
from paddle_tpu.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.ir.bucket_allreduce import ENV_BUCKET_MB, bucket_cap_bytes
from paddle_tpu.parallel import DistributedStrategy, fleet


def _fleet_mlp(depth=3, width=32, w_names=None):
    """MNIST-style MLP recipe built through fleet.distributed_optimizer so
    the per-grad c_allreduce_sum sync points exist."""
    fleet.init()
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', shape=[width], dtype='float32')
        y = layers.data('y', shape=[1], dtype='int64')
        h = x
        for _ in range(depth):
            h = layers.fc(h, size=width, act='relu')
        logits = layers.fc(h, size=10)
        loss = layers.reduce_mean(
            layers.softmax_with_cross_entropy(logits, y))
        fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1),
            strategy=DistributedStrategy()).minimize(loss)
    return main, start, loss


def _fleet_resnet_block():
    """ResNet bottleneck recipe (conv+BN+momentum) through fleet."""
    fleet.init()
    ch, hw = 8, 6
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', shape=[ch, hw, hw], dtype='float32')
        y = layers.data('y', shape=[1], dtype='float32')

        def conv_bn(inp, ch_out, k, act=None):
            c = layers.conv2d(inp, ch_out, k, padding=(k - 1) // 2,
                              bias_attr=False)
            return layers.batch_norm(c, act=act)

        h = conv_bn(x, ch // 2, 1, act='relu')
        h = conv_bn(h, ch // 2, 3, act='relu')
        h = conv_bn(h, ch, 1)
        h = layers.relu(layers.elementwise_add(h, x))
        pool = layers.reduce_mean(h, dim=[2, 3])
        pred = layers.fc(pool, size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fleet.distributed_optimizer(
            fluid.optimizer.Momentum(1e-2, momentum=0.9),
            strategy=DistributedStrategy()).minimize(loss)
    return main, start, loss


def _ar_ops(program, op_type='c_allreduce_sum'):
    return [o for o in program.global_block().ops if o.type == op_type]


# ---------------------------------------------------------------------------
# insertion
# ---------------------------------------------------------------------------

def test_fleet_minimize_inserts_grad_allreduce():
    main, _, _ = _fleet_mlp(depth=2)
    ops = _ar_ops(main)
    # one sync point per gradient, right after the backward marker
    from paddle_tpu.framework import BACKWARD_OP_TYPE
    blk_ops = main.global_block().ops
    bwd = next(i for i, o in enumerate(blk_ops)
               if o.type == BACKWARD_OP_TYPE)
    grads = blk_ops[bwd].outputs['Grads']
    assert len(ops) == len(grads) == 6          # 3 fc layers x (w, b)
    assert [o.inputs['x'][0] for o in blk_ops[bwd + 1:bwd + 1 + len(grads)]
            ] == list(grads)
    assert all(o.attrs['comm_dtype'] == 'f32' for o in ops)
    assert main._dist_fuse_all_reduce_ops is True


def test_fleet_k_step_schedules_skip_insertion():
    """Gradient-merge / local-SGD sync once per k steps — no per-step
    per-grad sync points are inserted for them."""
    fleet.init()
    for knob in ('gradient_merge_steps', 'local'):
        strat = DistributedStrategy()
        if knob == 'gradient_merge_steps':
            strat.gradient_merge_steps = 2
        else:
            strat.use_local_sgd = True
            strat.local_sgd_steps = 3
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            x = layers.data('x', shape=[4], dtype='float32')
            y = layers.data('y', shape=[1], dtype='float32')
            loss = layers.mean(layers.square_error_cost(
                layers.fc(x, 1), y))
            fleet.distributed_optimizer(
                fluid.optimizer.SGD(0.1), strategy=strat).minimize(loss)
        assert not _ar_ops(main), knob


def test_comm_dtype_stamped_from_strategy():
    fleet.init()
    strat = DistributedStrategy()
    strat.comm_dtype = 'int8'
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.data('y', shape=[1], dtype='float32')
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1), strategy=strat).minimize(loss)
    assert all(o.attrs['comm_dtype'] == 'int8' for o in _ar_ops(main))


# ---------------------------------------------------------------------------
# bucket formation
# ---------------------------------------------------------------------------

def test_bucket_count_matches_cap(monkeypatch):
    """Cap arithmetic: width*width f32 weight grads + width bias grads,
    cap = 2 weight grads -> ceil-ish grouping by cumulative bytes."""
    width = 32
    main, _, loss = _fleet_mlp(depth=4, width=width)
    assert len(_ar_ops(main)) == 10
    # cap: two full fc layers (w+b each) per bucket
    cap_mb = 2 * (width * width + width) * 4 / 2 ** 20
    monkeypatch.setenv(ENV_BUCKET_MB, str(cap_mb))
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name],
                                 build_strategy=bs)
    stats = ctx.stats['bucket_allreduce']
    assert stats['bucketed_ops'] == 10
    # 10 grads at ~2-layers-per-bucket: logits layer differs in size but
    # the grouping is deterministic — just pin the observed invariants
    buckets = _ar_ops(opt, 'c_allreduce_sum_bucket')
    assert stats['buckets'] == len(buckets) >= 3
    assert not _ar_ops(opt)                     # no per-grad ops left
    fused_inputs = [n for b in buckets for n in b.inputs['xs']]
    assert len(fused_inputs) == 10              # every grad exactly once
    per_bucket_bytes = []
    blk = opt.global_block()
    for b in buckets:
        per_bucket_bytes.append(sum(
            int(np.prod(blk.var(n).shape)) * 4 for n in b.inputs['xs']))
    assert all(nb <= bucket_cap_bytes() or len(b.inputs['xs']) == 1
               for nb, b in zip(per_bucket_bytes, buckets))


def test_pass_idempotent_and_gated(monkeypatch):
    main, _, loss = _fleet_mlp(depth=3)
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = True
    opt, _ = ir.apply_pipeline(main, fetch_names=[loss.name],
                               build_strategy=bs)
    n1 = len(opt.global_block().ops)
    # re-running the pipeline on the rewritten program changes nothing
    opt2, ctx2 = ir.apply_pipeline(opt, fetch_names=[loss.name],
                                   build_strategy=bs)
    assert len(opt2.global_block().ops) == n1
    assert 'bucket_allreduce' not in ctx2.stats
    # knob off -> untouched
    bs_off = BuildStrategy()
    bs_off.fuse_all_reduce_ops = False
    opt3, ctx3 = ir.apply_pipeline(main, fetch_names=[loss.name],
                                   build_strategy=bs_off)
    assert not _ar_ops(opt3, 'c_allreduce_sum_bucket')
    assert len(_ar_ops(opt3)) == len(_ar_ops(main))


def test_distributed_strategy_knob_reaches_pass_without_build_strategy():
    """Programs run WITHOUT a CompiledProgram still bucket via the fleet
    stamp; DistributedStrategy.fuse_all_reduce_ops=False disables it."""
    main, _, loss = _fleet_mlp(depth=3)
    opt, ctx = ir.apply_pipeline(main, fetch_names=[loss.name])
    assert _ar_ops(opt, 'c_allreduce_sum_bucket')        # stamp honored

    fleet.init()
    strat = DistributedStrategy()
    strat.fuse_all_reduce_ops = False
    main2, start2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, start2):
        x = layers.data('x', shape=[8], dtype='float32')
        y = layers.data('y', shape=[1], dtype='float32')
        h = layers.fc(x, 8, act='relu')
        loss2 = layers.mean(layers.square_error_cost(layers.fc(h, 1), y))
        fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1), strategy=strat).minimize(loss2)
    assert main2._dist_fuse_all_reduce_ops is False
    opt2, _ = ir.apply_pipeline(main2, fetch_names=[loss2.name])
    assert not _ar_ops(opt2, 'c_allreduce_sum_bucket')
    assert _ar_ops(opt2)                        # sync points still there


def test_bucket_cap_env_strict(monkeypatch):
    monkeypatch.setenv(ENV_BUCKET_MB, 'lots')
    with pytest.raises(ValueError, match=ENV_BUCKET_MB):
        bucket_cap_bytes()
    monkeypatch.setenv(ENV_BUCKET_MB, '-1')
    with pytest.raises(ValueError, match=ENV_BUCKET_MB):
        bucket_cap_bytes()
    monkeypatch.setenv(ENV_BUCKET_MB, '0.5')
    assert bucket_cap_bytes() == 2 ** 19


def test_bucket_metrics(monkeypatch):
    main, _, loss = _fleet_mlp(depth=3)
    monkeypatch.setenv(ENV_BUCKET_MB, '0.005')
    with obs.telemetry_guard(True):
        obs.reset()
        bs = BuildStrategy()
        bs.fuse_all_reduce_ops = True
        ir.apply_pipeline(main, fetch_names=[loss.name], build_strategy=bs)
        m = obs.registry.to_dict()
        assert sum(s['value']
                   for s in m['collective_allreduce_buckets']['samples']) \
            >= 2


# ---------------------------------------------------------------------------
# THE acceptance: bitwise pass-on/off parity at comm_dtype=f32
# ---------------------------------------------------------------------------

def _run_recipe(main, start, loss, feed, fuse_on, steps=5):
    from paddle_tpu.core.random import seed as set_seed
    bs = BuildStrategy()
    bs.fuse_all_reduce_ops = fuse_on
    exe = fluid.Executor()
    out = []
    with fluid.scope_guard(fluid.Scope()):
        set_seed(0)
        exe.run(start)
        cp = CompiledProgram(main, build_strategy=bs)
        for _ in range(steps):
            out.append(np.asarray(
                exe.run(cp, feed=feed, fetch_list=[loss])[0]))
    return out


@pytest.mark.parametrize('recipe', ['mnist_mlp', 'deep_wide_mlp',
                                    'resnet_block'])
def test_bitwise_parity_pass_on_off(recipe, monkeypatch):
    if recipe in ('mnist_mlp', 'deep_wide_mlp'):
        depth, width = (3, 32) if recipe == 'mnist_mlp' else (4, 64)
        main, start, loss = _fleet_mlp(depth=depth, width=width)
        rng = np.random.RandomState(0)
        feed = {'x': rng.randn(16, width).astype('float32'),
                'y': rng.randint(0, 10, (16, 1)).astype('int64')}
    else:
        main, start, loss = _fleet_resnet_block()
        rng = np.random.RandomState(0)
        feed = {'x': rng.randn(4, 8, 6, 6).astype('float32'),
                'y': rng.randn(4, 1).astype('float32')}
    # small cap => several buckets, so parity covers multi-bucket rewrites
    monkeypatch.setenv(ENV_BUCKET_MB, '0.005')
    off = _run_recipe(main, start, loss, feed, fuse_on=False)
    on = _run_recipe(main, start, loss, feed, fuse_on=True)
    for i, (a, b) in enumerate(zip(off, on)):
        assert np.array_equal(a, b), \
            f'{recipe}: step {i} loss differs pass-on vs pass-off'
