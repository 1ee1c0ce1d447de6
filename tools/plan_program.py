"""Static memory/cost planner CLI (paddle_tpu/analysis/plan.py).

Loads a saved inference model — or builds one of the tier-1 recipe
programs — and prints the memory plan: predicted peak HBM, the residency
breakdown (state/donation, feeds, activations-into-backward, gradients),
the top residents at the peak, and the per-op FLOP/byte cost ranking.
Milliseconds, zero tracing — nothing is compiled or executed.

    JAX_PLATFORMS=cpu python tools/plan_program.py --recipe mnist_mlp
    JAX_PLATFORMS=cpu python tools/plan_program.py --recipe bert_layer \
        --batch-size 64 --passes
    JAX_PLATFORMS=cpu python tools/plan_program.py --model-dir /m \
        --budget 2048
    JAX_PLATFORMS=cpu python tools/plan_program.py --decode-pool-mb 2048 \
        --kv-dtype int8

``--decode-pool-mb MB`` prints the decode KV pool sizing solve
(``serving.decode.layout.decode_pool_report``): the same arithmetic the
engine runs for ``PADDLE_TPU_DECODE_HBM_MB`` — model state subtracted from
the budget, the remainder divided by per-block KV bytes at ``--kv-dtype`` —
so the pool a budget buys is inspectable before serving starts.

``--budget MB`` gates the exit code: 1 when the predicted peak exceeds
it (CI memory regression guard), 0 otherwise. ``--passes`` plans the
post-IR-pipeline program (all fuse knobs on — what the executor actually
lowers); with ``PADDLE_TPU_HBM_BUDGET_MB`` set that includes the
``auto_remat`` rewrite, so the report shows the post-remat plan.
Exit code: 0 = within budget (or no budget), 1 = budget exceeded,
2 = usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
_TOOLS = os.path.join(_REPO, 'tools')
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)


def _decode_pool_doc(args):
    """The itemized PADDLE_TPU_DECODE_HBM_MB solve, as a plain dict."""
    from paddle_tpu.serving.decode.layout import decode_pool_report
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    cfg = (CausalLMConfig.tiny() if args.decode_model == 'tiny'
           else CausalLMConfig())
    report = decode_pool_report(TransformerLM(cfg), args.decode_pool_mb,
                                block_size=args.kv_block_size,
                                kv_dtype=args.kv_dtype)
    report['model'] = args.decode_model
    return report


def _format_decode_pool(doc):
    mib = 1 << 20
    yield (f"decode pool: {doc['num_blocks']} blocks of "
           f"{doc['block_size']} tokens at kv_dtype={doc['kv_dtype']} "
           f"({doc['model']} model)")
    yield (f"  budget {doc['budget_mb']} MiB - model state "
           f"{doc['model_state_bytes'] / mib:.1f} MiB -> "
           f"{doc['pool_bytes'] / mib:.1f} MiB of KV pages")
    yield (f"  block = {doc['kv_layers']} layers x {doc['block_size']} "
           f"tokens x {doc['row_bytes']} B a token a layer "
           f"({doc['kv_cache']}) = {doc['block_bytes']} B")


def main(argv=None):
    from lint_program import RECIPES, _build_recipe, _load_model

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group()
    src.add_argument('--model-dir',
                     help='saved inference model '
                          '(fluid.io.save_inference_model layout)')
    src.add_argument('--recipe', choices=RECIPES,
                     help='build one of the tier-1 recipe programs')
    ap.add_argument('--batch-size', type=int, default=16,
                    help='value substituted for dynamic (-1) batch dims '
                         '(default 16)')
    ap.add_argument('--budget', type=float, default=None,
                    help='HBM budget in MiB; exit 1 when the predicted '
                         'peak exceeds it')
    ap.add_argument('--passes', action='store_true',
                    help='plan the post-IR-pipeline program (fuse knobs '
                         'on; includes auto_remat when '
                         'PADDLE_TPU_HBM_BUDGET_MB is set)')
    ap.add_argument('--stages', type=int, default=None,
                    help='plan the program cut into N pipeline stages '
                         '(cost-model auto-cut, analysis.stage.'
                         'solve_stage_cuts) and print the per-stage '
                         'report; --budget then gates on the staged peak')
    ap.add_argument('--pp-schedule', choices=('gpipe', '1f1b',
                                              'interleaved'),
                    default='gpipe',
                    help='pipeline schedule the staged plan models '
                         '(default gpipe)')
    ap.add_argument('--pp-microbatches', type=int, default=None,
                    help='microbatch count for the staged plan; default '
                         'solves the smallest count that fits --budget '
                         '(analysis.stage.solve_microbatches), or the '
                         'stage count without a budget')
    ap.add_argument('--no-donate', action='store_true',
                    help='plan with buffer donation off '
                         '(PADDLE_TPU_DONATE=0 semantics)')
    ap.add_argument('--top', type=int, default=10,
                    help='rows in the residents / op-cost tables')
    ap.add_argument('--json', action='store_true',
                    help='emit the machine-readable plan')
    ap.add_argument('--decode-pool-mb', type=int, default=None,
                    help='print the decode KV pool sizing solve for this '
                         'HBM budget (MiB) — the PADDLE_TPU_DECODE_HBM_MB '
                         'arithmetic, itemized')
    ap.add_argument('--kv-dtype', choices=('f32', 'bf16', 'int8'),
                    default='f32',
                    help='KV pool storage dtype for the sizing solve '
                         '(PADDLE_TPU_KV_DTYPE; default f32)')
    ap.add_argument('--kv-block-size', type=int, default=16,
                    help='KV pool block size for the sizing solve '
                         '(default 16)')
    ap.add_argument('--decode-model', choices=('tiny', 'base'),
                    default='base',
                    help='CausalLM preset whose state/geometry the sizing '
                         'solve uses (default base)')
    args = ap.parse_args(argv)
    if args.batch_size <= 0:
        ap.error('--batch-size must be > 0')
    if args.stages is not None and args.stages < 2:
        ap.error('--stages must be >= 2')
    if args.pp_microbatches is not None and args.pp_microbatches <= 0:
        ap.error('--pp-microbatches must be > 0')
    if args.pp_microbatches is not None and args.stages is None:
        ap.error('--pp-microbatches requires --stages')
    if not (args.model_dir or args.recipe or args.decode_pool_mb):
        ap.error('one of --model-dir, --recipe or --decode-pool-mb '
                 'is required')
    if args.decode_pool_mb is not None and args.decode_pool_mb <= 0:
        ap.error('--decode-pool-mb must be > 0')
    if args.kv_block_size <= 0:
        ap.error('--kv-block-size must be > 0')

    os.environ.setdefault('PADDLE_TPU_VERIFY', 'full')
    from paddle_tpu.analysis.plan import plan_program

    pool_doc = _decode_pool_doc(args) if args.decode_pool_mb else None
    if not (args.model_dir or args.recipe):
        # decode-pool-only mode: no program to plan
        if args.json:
            print(json.dumps({'decode_pool': pool_doc}, indent=1))
        else:
            print('\n'.join(_format_decode_pool(pool_doc)))
        return 0

    if args.model_dir:
        program, fetches, feeds = _load_model(args.model_dir)
        label = args.model_dir
    else:
        program, fetches, feeds = _build_recipe(args.recipe)
        label = args.recipe

    if args.passes:
        from paddle_tpu import ir
        from paddle_tpu.compiler import BuildStrategy
        bs = BuildStrategy()
        bs.fuse_elewise_add_act_ops = True
        bs.fuse_all_optimizer_ops = True
        bs.fuse_all_reduce_ops = True
        program, _ctx = ir.apply_pipeline(program, fetch_names=fetches,
                                          feed_names=feeds,
                                          build_strategy=bs)

    plan = plan_program(program, fetch_names=fetches, feed_names=feeds,
                        donate=not args.no_donate,
                        assume_dim=args.batch_size)
    budget_bytes = int(args.budget * (1 << 20)) if args.budget else None

    splan = None
    if args.stages is not None:
        from paddle_tpu.analysis.stage import (plan_staged_program,
                                               solve_microbatches,
                                               solve_stage_cuts)
        cuts, _cut_report = solve_stage_cuts(
            program, args.stages, fetch_names=fetches, feed_names=feeds,
            assume_dim=args.batch_size)
        m = args.pp_microbatches
        if m is None:
            if budget_bytes:
                m, _peak, _fits = solve_microbatches(
                    program, cuts, args.pp_schedule, budget_bytes,
                    fetch_names=fetches, feed_names=feeds,
                    assume_dim=args.batch_size)
            else:
                m = args.stages
        splan = plan_staged_program(
            program, cuts, m, schedule=args.pp_schedule,
            fetch_names=fetches, feed_names=feeds,
            donate=not args.no_donate, assume_dim=args.batch_size)

    if args.json:
        doc = plan.to_dict(top=args.top)
        doc['target'] = label
        doc['batch_size'] = args.batch_size
        if budget_bytes:
            doc['budget_bytes'] = budget_bytes
            doc['fits_budget'] = plan.peak_bytes <= budget_bytes
        if splan is not None:
            doc['staged'] = splan.to_dict()
            if budget_bytes:
                doc['staged']['fits_budget'] = \
                    splan.host_peak_bytes <= budget_bytes
        if pool_doc:
            doc['decode_pool'] = pool_doc
        print(json.dumps(doc, indent=1))
    else:
        print(f'target: {label}  (batch dims assumed {args.batch_size}, '
              f'{plan.n_ops} ops, planned in '
              f'{plan.plan_seconds * 1e3:.1f}ms)')
        print('\n'.join(plan.format_report(top=args.top,
                                           budget_bytes=budget_bytes)))
        if splan is not None:
            print('\n'.join(splan.format_report(budget_bytes=budget_bytes)))
        if pool_doc:
            print('\n'.join(_format_decode_pool(pool_doc)))
    peak = splan.host_peak_bytes if splan is not None else plan.peak_bytes
    return 1 if budget_bytes and peak > budget_bytes else 0


if __name__ == '__main__':
    sys.exit(main())
