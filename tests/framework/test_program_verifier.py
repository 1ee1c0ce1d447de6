"""Static Program verifier (paddle_tpu/analysis/): seeded-defect corpus
(every diagnostic class, asserting code + op + construction site), the
zero-false-positive sweep over the tier-1 recipe programs (pre- and
post-pass-pipeline), pass post-condition enforcement (an intentionally
broken pass is caught AT THE PASS BOUNDARY naming the pass), Executor
pre-lowering validation at PADDLE_TPU_VERIFY=full, the inference-rule
lattice, and regression tests for the latent defects the verifier
surfaced (clone(for_test) dead vars, generated-layer dtype fallback,
lstm/gru optional slots)."""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, ir, layers as L
from paddle_tpu.analysis import (Diagnostic, ProgramVerificationError,
                                 UNKNOWN, VarInfo)
from paddle_tpu.analysis.infer import (InferError, broadcast_shapes,
                                       infer_op)
from paddle_tpu.compiler import BuildStrategy
from paddle_tpu.ir.pass_base import Pass, PassContext, PassManager
from paddle_tpu.ir import get_pass

from shared_programs import (build_bert_layer, build_mlp_adam,
                             build_resnet_block)

_THIS_FILE = os.path.abspath(__file__)


def _codes(diags):
    return [d.code for d in diags]


def _find(diags, code):
    hits = [d for d in diags if d.code == code]
    assert hits, f'no {code!r} diagnostic in {[d.format() for d in diags]}'
    return hits[0]


def _assert_site_here(diag):
    """Construction-site capture points into THIS test file."""
    assert diag.site is not None, diag.format()
    assert os.path.abspath(diag.site.rsplit(':', 1)[0]) == _THIS_FILE, \
        diag.site


# ---------------------------------------------------------------------------
# seeded-defect corpus: one program per defect class
# ---------------------------------------------------------------------------

def _prog():
    main, startup = fluid.Program(), fluid.Program()
    guard = fluid.program_guard(main, startup)
    guard.__enter__()
    return main, guard


def test_defect_read_before_write():
    main, g = _prog()
    try:
        L.data('x', [4], dtype='float32')
        blk = main.global_block()
        blk.create_var(name='ghost', shape=[4], dtype='float32')
        blk.append_op('relu', inputs={'x': 'ghost'}, outputs={'Out': 'o'})
        blk.create_var(name='o', shape=[4], dtype='float32')
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=['o']),
              'read-before-write')
    assert d.severity == 'error' and d.op_type == 'relu' \
        and d.var == 'ghost'
    _assert_site_here(d)


def test_defect_dangling_var():
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        main.global_block().append_op(
            'relu', inputs={'x': 'never_declared'},
            outputs={'Out': x.name})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=[x.name]),
              'dangling-var')
    assert d.severity == 'error' and d.var == 'never_declared'
    _assert_site_here(d)


def test_defect_shape_mismatch_matmul():
    main, g = _prog()
    try:
        L.data('a', [8, 3], dtype='float32', append_batch_size=False)
        L.data('b', [4, 5], dtype='float32', append_batch_size=False)
        blk = main.global_block()
        blk.create_var(name='mm', shape=None, dtype='float32')
        blk.append_op('matmul', inputs={'x': 'a', 'y': 'b'},
                      outputs={'Out': 'mm'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=['mm']),
              'shape-mismatch')
    assert d.severity == 'error' and d.op_type == 'matmul'
    assert 'K=3' in d.message and 'K=4' in d.message
    _assert_site_here(d)


def test_defect_bad_attr_cast_without_dtype():
    main, g = _prog()
    try:
        L.data('a', [8], dtype='float32')
        blk = main.global_block()
        blk.create_var(name='c', shape=None, dtype='float32')
        blk.append_op('cast', inputs={'x': 'a'}, outputs={'Out': 'c'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=['c']), 'bad-attr')
    assert d.severity == 'error' and d.op_type == 'cast'
    assert "'dtype'" in d.message
    _assert_site_here(d)


def test_defect_dtype_mismatch_hard_label():
    """softmax_with_cross_entropy with a FLOAT hard label — the op would
    gather with garbage indices at runtime."""
    main, g = _prog()
    try:
        logits = L.data('lg', [10], dtype='float32')
        lab = L.data('lb', [1], dtype='float32')       # wrong: float label
        blk = main.global_block()
        blk.create_var(name='loss', shape=None, dtype='float32')
        blk.create_var(name='sm', shape=None, dtype='float32')
        blk.append_op('softmax_with_cross_entropy',
                      inputs={'logits': logits.name, 'label': lab.name},
                      outputs={'Loss': 'loss', 'Softmax': 'sm'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=['loss']),
              'dtype-mismatch')
    assert d.severity == 'error' and 'soft_label' in d.message
    _assert_site_here(d)


def test_defect_unknown_op():
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        main.global_block().append_op('reluu', inputs={'x': x.name},
                                      outputs={'Out': x.name})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main), 'unknown-op')
    assert d.severity == 'error' and d.op_type == 'reluu'
    _assert_site_here(d)


def test_defect_dtype_decl_mismatch():
    main, g = _prog()
    try:
        x = L.data('x', [8], dtype='float32')
        blk = main.global_block()
        blk.create_var(name='w', shape=[-1, 8], dtype='int64')
        blk.append_op('relu', inputs={'x': x.name}, outputs={'Out': 'w'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=['w']),
              'dtype-decl-mismatch')
    assert d.severity == 'warning' and d.var == 'w'
    _assert_site_here(d)


def test_defect_dead_write():
    main, g = _prog()
    try:
        x = L.data('x', [8], dtype='float32')
        L.relu(x)                       # never read, never fetched
        out = L.scale(x, scale=2.0)
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=[out.name]),
              'dead-write')
    assert d.op_type == 'relu'
    _assert_site_here(d)


def test_defect_donated_fetch():
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=4)
        loss = L.reduce_mean(L.square_error_cost(h, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    finally:
        g.__exit__(None, None, None)
    pname = main.all_parameters()[0].name
    d = _find(analysis.verify_program(
        main, fetch_names=[loss.name, pname]), 'donated-fetch')
    assert d.severity == 'warning' and d.var == pname
    assert d.op_type == 'sgd'


def test_defect_bucket_mixed_dtype():
    main, g = _prog()
    try:
        a = L.data('a', [4], dtype='float32')
        b = L.data('b', [4], dtype='bfloat16')
        blk = main.global_block()
        blk.append_op('c_allreduce_sum_bucket',
                      inputs={'xs': [a.name, b.name]},
                      outputs={'Out': [a.name, b.name]})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=[a.name]),
              'dtype-mismatch')
    assert d.severity == 'error' and 'dtype-uniform' in d.message
    _assert_site_here(d)


def test_defect_comm_dtype_drift():
    main, g = _prog()
    try:
        a = L.data('a', [4], dtype='float32')
        b = L.data('b', [4], dtype='float32')
        blk = main.global_block()
        blk.append_op('c_allreduce_sum', inputs={'x': a.name},
                      outputs={'Out': a.name}, attrs={'comm_dtype': 'f32'})
        blk.append_op('c_allreduce_sum', inputs={'x': b.name},
                      outputs={'Out': b.name}, attrs={'comm_dtype': 'int8'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(
        main, fetch_names=[a.name, b.name]), 'comm-dtype-drift')
    assert d.severity == 'warning' and "'int8'" in d.message
    _assert_site_here(d)


def test_defect_bad_comm_dtype_attr():
    main, g = _prog()
    try:
        a = L.data('a', [4], dtype='float32')
        main.global_block().append_op(
            'c_allreduce_sum', inputs={'x': a.name},
            outputs={'Out': a.name}, attrs={'comm_dtype': 'fp8'})
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=[a.name]),
              'bad-attr')
    assert "'fp8'" in d.message


def test_defect_allreduce_under_kstep():
    """Per-grad c_allreduce_sum in a gradient-merge program: the sync
    belongs at the k-step boundary (fleet skips insertion there; a hand-
    built or badly-rewritten program must be flagged)."""
    main, g = _prog()
    try:
        x = L.data('x', [16], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=16, act='relu')
        out = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(out, y))
        opt = fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.SGD(0.1), k_steps=2)
        opt.minimize(loss)
    finally:
        g.__exit__(None, None, None)
    # seed the defect: insert a per-step allreduce after the marker
    from paddle_tpu.framework import BACKWARD_OP_TYPE, Operator
    blk = main.global_block()
    bwd = next(i for i, op in enumerate(blk.ops)
               if op.type == BACKWARD_OP_TYPE)
    grad = blk.ops[bwd].outputs['Grads'][0]
    blk.ops.insert(bwd + 1, Operator(
        blk, 'c_allreduce_sum', inputs={'x': grad}, outputs={'Out': grad},
        attrs={'axis': 'dp'}))
    d = _find(analysis.verify_program(main, fetch_names=[loss.name]),
              'allreduce-under-kstep')
    assert d.severity == 'warning'
    _assert_site_here(d)


def test_defect_rng_salt_missing_post_pass():
    main, g = _prog()
    try:
        x = L.data('x', [8], dtype='float32')
        h = L.dropout(x, dropout_prob=0.5)
    finally:
        g.__exit__(None, None, None)
    # pre stage: no complaint; post-pass stage: dropout lost its stamp
    assert 'rng-salt-missing' not in _codes(
        analysis.verify_program(main, fetch_names=[h.name]))
    d = _find(analysis.verify_program(
        main, fetch_names=[h.name], stage='post-pass'), 'rng-salt-missing')
    assert d.severity == 'warning' and d.op_type == 'dropout'


def test_defect_mixed_float_inputs():
    main, g = _prog()
    try:
        a = L.data('a', [8], dtype='float32')
        b = L.data('b', [8], dtype='bfloat16')
        c = L.elementwise_add(a, b)
    finally:
        g.__exit__(None, None, None)
    d = _find(analysis.verify_program(main, fetch_names=[c.name]),
              'mixed-float-inputs')
    assert d.severity == 'warning'
    # the same program under an AMP config is intentional → clean
    main._amp_config = {'white': set(), 'black': set(), 'dtype': None}
    assert 'mixed-float-inputs' not in _codes(
        analysis.verify_program(main, fetch_names=[c.name]))


def test_defect_missing_required_input():
    main, g = _prog()
    try:
        blk = main.global_block()
        blk.create_var(name='o', shape=[4, 4], dtype='float32')
        blk.append_op('matmul', inputs={}, outputs={'Out': 'o'})
    finally:
        g.__exit__(None, None, None)
    diags = analysis.verify_program(main, fetch_names=['o'])
    assert 'missing-input' in _codes(diags)
    assert _find(diags, 'missing-input').severity == 'error'


# ---------------------------------------------------------------------------
# zero-false-positive sweep: every tier-1 recipe, pre- and post-pipeline
# ---------------------------------------------------------------------------

def _fused_bs():
    bs = BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    bs.fuse_all_optimizer_ops = True
    bs.fuse_all_reduce_ops = True
    return bs


def _mnist_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = L.data('img', [64], dtype='float32')
        label = L.data('label', [1], dtype='int64')
        h = L.fc(img, size=32, act='relu')
        h = L.fc(h, size=32, act='relu')
        logits = L.fc(h, size=10)
        loss = L.reduce_mean(L.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, [loss.name], ['img', 'label']


def _fleet_dp():
    from paddle_tpu.parallel import DistributedStrategy, fleet
    fleet.init()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', shape=[32], dtype='float32')
        y = L.data('y', shape=[1], dtype='int64')
        h = L.fc(x, size=32, act='relu')
        h2 = L.fc(h, size=32, act='relu')
        logits = L.fc(h2, size=10)
        loss = L.reduce_mean(L.softmax_with_cross_entropy(logits, y))
        fleet.distributed_optimizer(
            fluid.optimizer.SGD(0.1),
            strategy=DistributedStrategy()).minimize(loss)
    return main, [loss.name], ['x', 'y']


def _decode_engine_prog():
    """Static decode-flavored program: embedding lookup + fc + softmax +
    greedy argmax over logits — the per-step program shape of the decode
    path, including an int64 id feed and an int64 argmax output."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [8], dtype='int64')
        emb = L.embedding(ids, size=[100, 16])
        h = L.fc(emb, size=16, act='tanh')
        logits = L.fc(h, size=100)
        nxt = L.argmax(logits, axis=-1)
    return main, [nxt.name], ['ids']


def _deepfm_sparse():
    """Static DeepFM over sparse id features: both embedding tables take
    the rows-only gradient path (is_sparse=True → padded-COO marker
    outputs + sparse_* update ops, docs/SPARSE.md) — the 7th recipe, so
    the sweep covers the sparse op family end to end."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('feat_ids', [8], dtype='int64')
        vals = L.data('feat_vals', [8], dtype='float32')
        label = L.data('ctr', [1], dtype='float32')
        w1 = L.embedding(ids, size=[500, 1], is_sparse=True)
        emb = L.embedding(ids, size=[500, 8], is_sparse=True)
        v3 = L.unsqueeze(vals, axes=[2])
        first = L.reduce_sum(w1 * v3, dim=1)
        e = emb * v3
        sum_sq = L.square(L.reduce_sum(e, dim=1))
        sq_sum = L.reduce_sum(L.square(e), dim=1)
        second = 0.5 * L.reduce_sum(sum_sq - sq_sum, dim=1, keep_dim=True)
        deep = L.fc(e, size=16, act='relu')
        logit = L.fc(L.concat([first, second, deep], axis=1), size=1)
        loss = L.reduce_mean(
            L.sigmoid_cross_entropy_with_logits(logit, label))
        fluid.optimizer.Adagrad(0.05).minimize(loss)
    return main, [loss.name], ['feat_ids', 'feat_vals', 'ctr']


_RECIPES = {
    'mnist_mlp': _mnist_mlp,
    'mlp_adam': lambda: _from_builder(build_mlp_adam),
    'resnet_block': lambda: _from_builder(build_resnet_block),
    'bert_layer': lambda: _from_builder(build_bert_layer),
    'fleet_dp': _fleet_dp,
    'decode_engine': _decode_engine_prog,
    'deepfm_sparse': _deepfm_sparse,
}


def _from_builder(builder):
    main, _startup, make_feed, fetch = builder()
    feed = make_feed() if callable(make_feed) else make_feed
    return main, [fetch.name], sorted(feed)


@pytest.mark.parametrize('name', sorted(_RECIPES))
def test_recipe_sweep_no_findings(name):
    """The acceptance bar: zero diagnostics of severity ≥ warning on
    every tier-1 recipe program, both before the pass pipeline and on
    its final output."""
    main, fetches, feeds = _RECIPES[name]()
    pre = analysis.verify_program(main, fetch_names=fetches,
                                  feed_names=feeds)
    bad = analysis.severity_at_least(pre, 'warning')
    assert not bad, '\n'.join(d.format() for d in bad)

    opt, _ = ir.apply_pipeline(main, fetch_names=fetches,
                               feed_names=feeds, build_strategy=_fused_bs())
    post = analysis.verify_program(opt, fetch_names=fetches,
                                   feed_names=feeds, stage='post-pipeline')
    bad = analysis.severity_at_least(post, 'warning')
    assert not bad, '\n'.join(d.format() for d in bad)


# ---------------------------------------------------------------------------
# pass post-condition: a broken pass is caught at its own boundary
# ---------------------------------------------------------------------------

class _BrokenRenamePass(Pass):
    """Test-only: rewrites the last op to read a nonexistent var."""
    name = 'test_broken_rename'
    order = 500

    def apply_impl(self, program, ctx):
        op = program.global_block().ops[-1]
        for k in op.inputs:
            op.inputs[k] = ['__not_a_var__']
        return True


class _BrokenProducerDropPass(Pass):
    """Test-only: deletes an op whose output a later op still reads."""
    name = 'test_broken_drop'
    order = 500

    def apply_impl(self, program, ctx):
        blk = program.global_block()
        blk.ops = [op for i, op in enumerate(blk.ops) if i != 0]
        return True


def _small_prog():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [8], dtype='float32')
        h = L.fc(x, size=4, act='relu')
        loss = L.reduce_mean(h)
    return main, loss


@pytest.mark.parametrize('broken_cls', [_BrokenRenamePass,
                                        _BrokenProducerDropPass])
def test_broken_pass_caught_at_boundary(monkeypatch, broken_cls):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main, loss = _small_prog()
    mgr = PassManager([get_pass('constant_fold'), broken_cls(),
                       get_pass('dce')])
    with pytest.raises(ProgramVerificationError) as ei:
        mgr.apply(main, PassContext(fetch_names=[loss.name],
                                    feed_names=['x']))
    assert ei.value.pass_name == broken_cls.name
    assert broken_cls.name in str(ei.value)
    assert ei.value.diagnostics           # the offending diagnostic rides


def test_broken_pass_not_blamed_for_preexisting_errors(monkeypatch):
    """Post-condition is 'no NEW errors': a pass that does not touch an
    already-broken region passes its boundary check."""
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main, loss = _small_prog()
    blk = main.global_block()
    # pre-existing defect, present BEFORE the pipeline runs
    from paddle_tpu.framework import Operator
    blk.ops.append(Operator(blk, 'relu', inputs={'x': '__preexisting__'},
                            outputs={'Out': loss.name}))
    mgr = PassManager([get_pass('constant_fold')])
    mgr.apply(main, PassContext(fetch_names=[loss.name],
                                feed_names=['x']))    # must not raise


def test_clean_pipeline_verifies_quietly(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main, fetches, feeds = _mnist_mlp()
    opt, _ = ir.apply_pipeline(main, fetch_names=fetches, feed_names=feeds,
                               build_strategy=_fused_bs())
    assert opt.num_ops() > 0


# ---------------------------------------------------------------------------
# executor integration: PADDLE_TPU_VERIFY=full pre-lowering validation
# ---------------------------------------------------------------------------

def test_executor_full_mode_rejects_malformed_program(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'full')
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        blk = main.global_block()
        blk.create_var(name='o', shape=[-1, 4], dtype='float32')
        blk.append_op('relu', inputs={'x': 'missing_var'},
                      outputs={'Out': 'o'})
    finally:
        g.__exit__(None, None, None)
    exe = fluid.Executor()
    with pytest.raises(ProgramVerificationError) as ei:
        exe.run(main, feed={'x': np.zeros((2, 4), np.float32)},
                fetch_list=['o'])
    msg = str(ei.value)
    assert 'missing_var' in msg and 'relu' in msg
    assert os.path.basename(__file__) in msg     # construction site


def test_executor_full_mode_runs_clean_program(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'full')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [4], dtype='float32')
        h = L.fc(x, size=3, act='relu')
    exe = fluid.Executor()
    exe.run(startup)
    out, = exe.run(main, feed={'x': np.ones((2, 4), np.float32)},
                   fetch_list=[h])
    assert out.shape == (2, 3)


@pytest.mark.parametrize('level, ran', [('off', False), ('passes', True)])
def test_executor_run_verifies_at_passes_level_only(monkeypatch, level, ran):
    """The verifier rides the REAL Executor build: a cold ``exe.run`` of the
    multi-param Adam recipe records ``program_verify_seconds`` beside its
    ``executor_compile_seconds`` at level ``passes``, and nothing at
    ``off``."""
    from paddle_tpu import observability as obs
    monkeypatch.setenv('PADDLE_TPU_VERIFY', level)
    main, startup, make_feed, loss = build_mlp_adam()
    with obs.telemetry_guard(True):
        obs.reset()
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=make_feed(), fetch_list=[loss])
        d = obs.registry.to_dict()

    def count(name):
        return sum(x['count'] for x in d.get(name, {}).get('samples', []))

    assert count('executor_compile_seconds') >= 1
    assert (count('program_verify_seconds') > 0) is ran


def test_trace_error_names_op_and_site(monkeypatch):
    """At passes level a PRE-EXISTING defect is not raised at the pass
    boundary (no-NEW-errors contract) — the trace then fails, and the
    exception carries the op type + construction site annotation."""
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main, g = _prog()
    try:
        L.data('a', [8, 3], dtype='float32', append_batch_size=False)
        L.data('b', [4, 5], dtype='float32', append_batch_size=False)
        blk = main.global_block()
        blk.create_var(name='mm', shape=None, dtype='float32')
        blk.append_op('matmul', inputs={'x': 'a', 'y': 'b'},
                      outputs={'Out': 'mm'})
    finally:
        g.__exit__(None, None, None)
    exe = fluid.Executor()
    with pytest.raises(Exception) as ei:
        exe.run(main, feed={'a': np.zeros((8, 3), np.float32),
                            'b': np.zeros((4, 5), np.float32)},
                fetch_list=['mm'])
    e = ei.value
    rendered = ' '.join([str(e)] + list(getattr(e, '__notes__', [])))
    assert "while lowering op 'matmul'" in rendered
    assert os.path.basename(__file__) in rendered     # construction site


def test_verify_level_strict_parse(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'everything')
    with pytest.raises(ValueError, match='PADDLE_TPU_VERIFY'):
        analysis.verify_level()
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'off')
    assert analysis.verify_level() == 'off'
    monkeypatch.delenv('PADDLE_TPU_VERIFY')
    assert analysis.verify_level() == 'off'


def test_site_capture_gated_by_env(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'off')
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        h = L.relu(x)
    finally:
        g.__exit__(None, None, None)
    assert all(op._site is None for op in main.global_block().ops)

    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main2, g = _prog()
    try:
        x = L.data('x2', [4], dtype='float32')
        h = L.relu(x)                                     # noqa: F841
    finally:
        g.__exit__(None, None, None)
    sites = [op._site for op in main2.global_block().ops]
    assert all(s is not None for s in sites)
    assert all(os.path.abspath(s.rsplit(':', 1)[0]) == _THIS_FILE
               for s in sites)


def test_clone_preserves_sites(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_VERIFY', 'passes')
    main, g = _prog()
    try:
        x = L.data('x', [4], dtype='float32')
        L.relu(x)
    finally:
        g.__exit__(None, None, None)
    clone = main.clone()
    for a, b in zip(main.global_block().ops, clone.global_block().ops):
        assert b._site == a._site


# ---------------------------------------------------------------------------
# inference-rule engine unit tests: the UNKNOWN lattice
# ---------------------------------------------------------------------------

def test_unknown_dims_never_poison():
    # dynamic batch broadcasts with anything
    assert broadcast_shapes((UNKNOWN, 4), (1, 4)) == (UNKNOWN, 4)
    assert broadcast_shapes((UNKNOWN, 4), (8, 1)) == (8, 4)
    with pytest.raises(InferError):
        broadcast_shapes((3, 4), (5, 4))


def test_varinfo_numel_and_display():
    v = VarInfo((-1, 8), 'float32')
    assert v.shape == (UNKNOWN, 8)
    assert v.numel() is None
    assert v.display_shape() == (-1, 8)
    assert VarInfo((2, 3), 'float32').numel() == 6


def _one_op_infer(op_type, inputs, attrs, outputs=('Out',), n_out=None):
    main, g = _prog()
    try:
        blk = main.global_block()
        env = {}
        for name, (shape, dtype) in inputs.items():
            blk.create_var(name=name, shape=shape, dtype=dtype)
            env[name] = VarInfo(shape, dtype)
        in_map = {}
        for slot, names in attrs.pop('__slots__').items():
            in_map[slot] = names
        out_map = {s: (n_out or {}).get(s, [f'{s}_out'])
                   for s in outputs}
        op = blk.append_op(op_type, inputs=in_map, outputs=out_map,
                           attrs=attrs)
        return infer_op(op, env, blk)
    finally:
        g.__exit__(None, None, None)


def test_rule_matmul_dynamic_batch():
    r = _one_op_infer('matmul',
                      {'a': ((-1, 16), 'float32'), 'b': ((16, 4), 'float32')},
                      {'__slots__': {'x': ['a'], 'y': ['b']}})
    assert r['Out'].shape == (UNKNOWN, 4)
    assert r['Out'].dtype == 'float32'


def test_rule_reshape_infers_minus_one():
    r = _one_op_infer('reshape', {'a': ((6, 4), 'float32')},
                      {'shape': [-1, 8], '__slots__': {'x': ['a']}})
    assert r['Out'].shape == (3, 8)
    with pytest.raises(InferError):
        _one_op_infer('reshape', {'a': ((6, 4), 'float32')},
                      {'shape': [5, 5], '__slots__': {'x': ['a']}})


def test_rule_concat_and_split():
    r = _one_op_infer('concat',
                      {'a': ((2, 3), 'float32'), 'b': ((4, 3), 'float32')},
                      {'axis': 0, '__slots__': {'xs': ['a', 'b']}})
    assert r['Out'].shape == (6, 3)
    with pytest.raises(InferError):
        _one_op_infer('concat',
                      {'a': ((2, 3), 'float32'), 'b': ((4, 5), 'float32')},
                      {'axis': 0, '__slots__': {'xs': ['a', 'b']}})
    r = _one_op_infer('split', {'a': ((2, 12), 'float32')},
                      {'num_or_sections': 3, 'dim': -1,
                       '__slots__': {'x': ['a']}},
                      n_out={'Out': ['s0', 's1', 's2']})
    assert [v.shape for v in r['Out']] == [(2, 4)] * 3


def test_rule_conv2d_shape():
    r = _one_op_infer('conv2d',
                      {'x': ((-1, 3, 8, 8), 'float32'),
                       'w': ((16, 3, 3, 3), 'float32')},
                      {'stride': 1, 'padding': 1,
                       '__slots__': {'x': ['x'], 'weight': ['w']}})
    assert r['Out'].shape == (UNKNOWN, 16, 8, 8)
    with pytest.raises(InferError):
        _one_op_infer('conv2d',
                      {'x': ((-1, 4, 8, 8), 'float32'),
                       'w': ((16, 3, 3, 3), 'float32')},
                      {'__slots__': {'x': ['x'], 'weight': ['w']}})


def test_rule_coverage_over_recipe_ops():
    """Every op type the tier-1 recipes emit has an inference rule —
    the coverage contract docs/ANALYSIS.md promises."""
    needed = set()
    for name, build in _RECIPES.items():
        main, _f, _d = build()
        for b in main.blocks:
            for op in b.ops:
                needed.add(op.type)
    from paddle_tpu.analysis import has_rule
    from paddle_tpu.framework import BACKWARD_OP_TYPE
    special = {BACKWARD_OP_TYPE}
    missing = {t for t in needed - special if not has_rule(t)}
    assert not missing, f'recipe ops without infer rules: {sorted(missing)}'


# ---------------------------------------------------------------------------
# regressions for latent defects the verifier surfaced
# ---------------------------------------------------------------------------

def test_regression_clone_for_test_drops_dead_grad_vars():
    """clone(for_test=True) used to keep the backward tail's @GRAD vars
    as dead declarations in every eval/inference program."""
    main, g = _prog()
    try:
        x = L.data('x', [16], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = L.fc(x, size=16, act='relu')
        out = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(out, y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    finally:
        g.__exit__(None, None, None)
    test_prog = main.clone(for_test=True)
    names = set(test_prog.global_block().vars)
    assert not any(n.endswith('@GRAD') for n in names)
    diags = analysis.verify_program(test_prog, fetch_names=[out.name])
    assert 'dead-var' not in _codes(diags)
    # parameters and data vars survive the sweep
    assert all(p.name in names for p in main.all_parameters())
    assert 'x' in names and 'y' in names


def test_regression_static_dtype_fallback_for_unknown_shapes():
    """Generated layers used to declare their output with the INPUT's
    dtype whenever eval_shape could not run (unknown input shape);
    arg_max then carried a float32 declaration for an int64 result."""
    main, g = _prog()
    try:
        blk = main.global_block()
        from paddle_tpu.framework import Variable
        v = blk.create_var(name='mystery', shape=None, dtype='float32')
        out = L.argmax(v, axis=-1)
    finally:
        g.__exit__(None, None, None)
    assert out.dtype == 'int64'


def test_regression_lstm_gru_optional_initial_state():
    """lstm/gru tolerate absent h0/c0 at runtime; the registry now says
    so, and the verifier no longer flags recurrent layers built without
    an initial state."""
    from paddle_tpu.ops.registry import get_op
    assert {'h0', 'c0'} <= get_op('lstm').optional
    assert 'h0' in get_op('gru').optional
    main, g = _prog()
    try:
        x = L.data('x', [5, 12], dtype='float32')
        proj = L.fc(x, size=12, num_flatten_dims=2)
        hidden, _cell = L.dynamic_lstm(proj, size=12)
    finally:
        g.__exit__(None, None, None)
    diags = analysis.verify_program(main, fetch_names=[hidden.name])
    assert 'missing-input' not in _codes(diags)


def test_regression_dce_keeps_cond_writes_producer():
    """DCE used to drop the producer of a cond `writes` var that nothing
    else read — but _run_cond reads the OUTER value for the branch that
    leaves the var untouched, so the lowered program died at trace time
    with a bare KeyError. _op_read_names now counts control-flow
    passthrough reads (found via the verifier's dataflow model)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [4], dtype='float32')
        pred = L.reduce_sum(x) > 0.0
        t = L.scale(x, scale=3.0)       # read only by the cond passthrough

        def true_fn():
            L.assign(L.scale(x, 2.0), output=t)
            return L.scale(x, 1.0)

        def false_fn():
            return L.scale(x, 0.5)

        r = L.cond(pred, true_fn, false_fn)
        final = L.reduce_sum(r)
    # DCE (default pipeline) must keep the scale producer alive
    opt, _ = ir.apply_pipeline(main, fetch_names=[final.name],
                               feed_names=['x'])
    kept = [op for op in opt.global_block().ops
            if op.type == 'scale' and op.outputs['Out'] == [t.name]]
    assert kept, 'DCE dropped the cond-writes producer again'
    exe = fluid.Executor()
    out, = exe.run(main, feed={'x': np.ones((2, 4), np.float32)},
                   fetch_list=[final])
    assert out == pytest.approx(8.0)    # true branch: sum(2x) over 8 ones


def test_register_op_rejects_unknown_optional_slot():
    from paddle_tpu.ops.registry import register_op
    with pytest.raises(ValueError, match='optional'):
        @register_op('___opt_probe___', optional=('nope',))
        def f(x):
            return x
