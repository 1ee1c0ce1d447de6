"""Static Program linter CLI (paddle_tpu/analysis/).

Loads a saved inference model — or builds one of the tier-1 recipe
programs — and prints the full verifier diagnostic report: shape/dtype
inference findings, dataflow errors (read-before-write, dangling vars),
dead code, collective consistency, and donation hazards, each with the
op and its Python construction site.

    JAX_PLATFORMS=cpu python tools/lint_program.py --recipe mnist_mlp
    JAX_PLATFORMS=cpu python tools/lint_program.py --model-dir /path/to/model
    JAX_PLATFORMS=cpu python tools/lint_program.py --recipe bert_layer \
        --passes --json

``--passes`` additionally runs the IR pass pipeline (all fuse knobs on)
and re-verifies the rewritten program — the same post-condition the
executor applies at ``PADDLE_TPU_VERIFY=passes``.

Exit code: 0 = nothing at/above ``--fail-on`` (default ``error``),
1 = findings, 2 = usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

RECIPES = ('mnist_mlp', 'mlp_adam', 'resnet_block', 'bert_layer',
           'fleet_dp', 'seq2seq_decode')


def _build_recipe(name):
    """(main_program, fetch_names, feed_names) for one tier-1 recipe."""
    import paddle_tpu as fluid
    from paddle_tpu import layers as L
    # the tier-1 recipe programs are the test suites' own
    sys.path.insert(0, os.path.join(_REPO, 'tests', 'framework'))
    from shared_programs import (build_bert_layer, build_mlp_adam,
                                 build_resnet_block)

    if name == 'mnist_mlp':
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
        return main, [loss.name], ['img', 'label']
    if name in ('mlp_adam', 'resnet_block', 'bert_layer'):
        builder = {'mlp_adam': build_mlp_adam,
                   'resnet_block': build_resnet_block,
                   'bert_layer': build_bert_layer}[name]
        main, _startup, make_feed, fetch = builder()
        feed = make_feed()
        return main, [fetch.name], sorted(feed)
    if name == 'fleet_dp':
        from paddle_tpu.parallel import DistributedStrategy, fleet
        fleet.init()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = L.data('x', shape=[32], dtype='float32')
            y = L.data('y', shape=[1], dtype='int64')
            h = L.fc(x, size=32, act='relu')
            logits = L.fc(h, size=10)
            loss = L.reduce_mean(
                L.softmax_with_cross_entropy(logits, y))
            fleet.distributed_optimizer(
                fluid.optimizer.SGD(0.1),
                strategy=DistributedStrategy()).minimize(loss)
        return main, [loss.name], ['x', 'y']
    if name == 'seq2seq_decode':
        main, fetches, feeds = _build_seq2seq()
        return main, fetches, feeds
    raise SystemExit(f'unknown recipe {name!r}; choose from {RECIPES}')


def _build_seq2seq():
    """Static greedy-decode-style program: embedding + fixed-trip RNN
    loop over a while op — the control-flow shape the decode path emits."""
    import paddle_tpu as fluid
    from paddle_tpu import layers as L
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data('ids', [8], dtype='int64')
        emb = L.embedding(ids, size=[100, 16])
        h = L.fc(emb, size=16, act='tanh')
        logits = L.fc(h, size=100)
        probs = L.softmax(logits)
    return main, [probs.name], ['ids']


def _load_model(dirname):
    import paddle_tpu as fluid
    exe = fluid.Executor()
    program, feed_names, fetch_targets = fluid.io.load_inference_model(
        dirname, exe)
    return program, [t.name for t in fetch_targets], list(feed_names)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument('--model-dir',
                     help='saved inference model (fluid.io.'
                          'save_inference_model layout)')
    src.add_argument('--recipe', choices=RECIPES,
                     help='build one of the tier-1 recipe programs')
    ap.add_argument('--passes', action='store_true',
                    help='also run the IR pass pipeline (fuse knobs on) '
                         'and re-verify the rewritten program')
    ap.add_argument('--plan', action='store_true',
                    help='append the static memory plan (peak HBM, top '
                         'residents, op cost ranking — '
                         'tools/plan_program.py report)')
    ap.add_argument('--batch-size', type=int, default=16,
                    help='dynamic-dim substitution for --plan '
                         '(default 16)')
    ap.add_argument('--json', action='store_true',
                    help='emit machine-readable diagnostics')
    ap.add_argument('--fail-on', choices=('info', 'warning', 'error'),
                    default='error',
                    help='exit 1 when diagnostics at/above this severity '
                         'exist (default: error)')
    args = ap.parse_args(argv)

    # site capture must be on while the recipe builds its ops
    os.environ.setdefault('PADDLE_TPU_VERIFY', 'full')
    from paddle_tpu import analysis

    if args.model_dir:
        program, fetches, feeds = _load_model(args.model_dir)
        label = args.model_dir
    else:
        program, fetches, feeds = _build_recipe(args.recipe)
        label = args.recipe

    reports = [('pre-lower', analysis.verify_program(
        program, fetch_names=fetches, feed_names=feeds, stage='pre'))]
    if args.passes:
        from paddle_tpu import ir
        from paddle_tpu.compiler import BuildStrategy
        bs = BuildStrategy()
        bs.fuse_elewise_add_act_ops = True
        bs.fuse_all_optimizer_ops = True
        bs.fuse_all_reduce_ops = True
        opt, _ctx = ir.apply_pipeline(program, fetch_names=fetches,
                                      feed_names=feeds, build_strategy=bs)
        reports.append(('post-pipeline', analysis.verify_program(
            opt, fetch_names=fetches, feed_names=feeds,
            stage='post-pipeline')))

    plan = None
    if args.plan:
        from paddle_tpu.analysis.plan import plan_program
        plan = plan_program(program, fetch_names=fetches,
                            feed_names=feeds,
                            assume_dim=args.batch_size)

    all_diags = [d for _, ds in reports for d in ds]
    if args.json:
        doc = {
            'target': label,
            'stages': {stage: [d.to_dict() for d in ds]
                       for stage, ds in reports},
            'max_severity': analysis.max_severity(all_diags),
        }
        if plan is not None:
            doc['plan'] = plan.to_dict()
        print(json.dumps(doc, indent=1))
    else:
        for stage, ds in reports:
            print(analysis.format_report(
                ds, f'{label} [{stage}]: {len(ds)} finding(s)'))
        if plan is not None:
            print('\n'.join(plan.format_report()))
    return 1 if analysis.severity_at_least(all_diags, args.fail_on) else 0


if __name__ == '__main__':
    sys.exit(main())
