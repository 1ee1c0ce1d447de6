"""Benchmark driver: ResNet-50 fwd+bwd+update images/sec/chip (bf16 compute)
plus BERT-base pretrain seq/s and MFU for both (SURVEY §5 metrics).

Output protocol:
- each metric is printed as its OWN JSON line the moment it is measured,
  flushed, so a mid-run crash still leaves every completed number on stdout;
- the LAST line is the combined summary in the original driver contract
  {"metric", "value", "unit", "vs_baseline", ...};
- the backend is initialised in this process, once; a platform that cannot
  start raises;
- a failing bench section prints its own error line and the run exits
  nonzero only AFTER printing whatever was measured;
- a `dygraph_eager_overhead` line (valid on CPU too) carries the dispatch
  microbench from tools/bench_dispatch.py: eager tape step with the per-op
  kernel cache off/on vs the fused TrainStep, slope-method ms/step for a
  ResNet bottleneck block and a BERT layer (PERF.md §9).

Baseline (BASELINE.json north star): CUDA V100 ResNet-50 ≈ 383 img/s fp32
(PaddlePaddle's published reference-class number for the 1.x benchmark suite).

MFU = delivered FLOP/s ÷ chip peak bf16 FLOP/s, with analytic model FLOPs:
- ResNet-50 @224: ≈ 4.09 GFLOP fwd/img (2×MACs) → ×3 for fwd+bwd ≈ 12.3 GF.
- BERT: 6·P FLOP per token (P = non-embedding params, train fwd+bwd)
  + 12·L·h·S per token of attention score/context work (see PERF.md).
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np


def emit(obj):
    """One JSON object per line, flushed immediately (partial-evidence
    protocol: anything measured survives a later crash)."""
    print(json.dumps(obj), flush=True)


V100_BASELINE_IMG_S = 383.0
RESNET50_TRAIN_GFLOP_PER_IMG = 12.3

# chip peak bf16 TFLOP/s by device_kind substring (dense, no sparsity)
_CHIP_PEAK_TFLOPS = [
    ('v6', 918.0), ('v5p', 459.0), ('v5 lite', 197.0), ('v5e', 197.0),
    ('v4', 275.0), ('v3', 123.0), ('v2', 45.0),
]


def chip_peak_tflops(device):
    kind = getattr(device, 'device_kind', '').lower()
    for sub, peak in _CHIP_PEAK_TFLOPS:
        if sub in kind:
            return peak
    return None


def _resnet_rate(on_tpu, batch, img, iters, fmt, s2d):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.models import ResNet50
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.tape import dispatch_op

    with dygraph.guard():
        model = ResNet50(class_dim=1000, data_format=fmt,
                         stem_space_to_depth=s2d)
        opt = fluid.optimizer.Momentum(0.1, momentum=0.9,
                                       parameter_list=model.parameters())

        def loss_fn(m, x, y):
            logits = m(x)
            logits = dispatch_op('cast', {'x': logits}, {'dtype': 'float32'})
            l, _ = dispatch_op('softmax_with_cross_entropy',
                               {'logits': logits, 'label': y}, {})
            return dispatch_op('reduce_mean', {'x': l}, {})

        # bf16 compute with fp32 master weights (AMP) on TPU; param dtypes
        # stay fp32 across steps so the fused step compiles exactly once
        step = TrainStep(model, loss_fn, opt,
                         amp_dtype=jnp.bfloat16 if on_tpu else None)
        xshape = (batch, 3, img, img) if fmt == 'NCHW' \
            else (batch, img, img, 3)
        x = np.random.randn(*xshape).astype(np.float32)
        y = np.random.randint(0, 1000, (batch, 1)).astype(np.int64)
        if on_tpu:
            x = jnp.asarray(x, jnp.bfloat16)

        jax.block_until_ready(step(x, y))      # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            l = step(x, y)
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0
    return batch * iters / dt


def bench_resnet(on_tpu):
    batch = 128 if on_tpu else 8
    img = 224 if on_tpu else 32
    iters = 20 if on_tpu else 3
    # NHWC on TPU: convs lower without layout transposes — measured ~6%
    # faster end-to-end than NCHW on v5e (PERF.md §2)
    fmt = 'NHWC' if on_tpu else 'NCHW'
    rate = _resnet_rate(on_tpu, batch, img, iters, fmt, s2d=False)
    if on_tpu and os.environ.get('PADDLE_TPU_STEM_S2D', '1') != '0':
        # self-measuring A/B of the space-to-depth stem (PERF.md §8): one
        # extra compile+short run; the headline stays the measured winner
        # and both numbers land in the captured evidence. The plain rate
        # is already measured — an A/B failure must not lose it (the
        # partial-evidence protocol this file promises).
        try:
            rate_s2d = _resnet_rate(on_tpu, batch, img,
                                    max(iters // 2, 5), fmt, s2d=True)
        except Exception as e:
            emit({"metric": "resnet50_stem_s2d_ab",
                  "plain_img_per_sec": round(rate, 2),
                  "error": f"{type(e).__name__}: {e}"[:500]})
        else:
            emit({"metric": "resnet50_stem_s2d_ab",
                  "plain_img_per_sec": round(rate, 2),
                  "s2d_img_per_sec": round(rate_s2d, 2),
                  "winner": "s2d" if rate_s2d > rate else "plain"})
            rate = max(rate, rate_s2d)
    return rate


def bench_bert(on_tpu):
    """BERT-base MLM+NSP pretrain step, bf16, XLA attention —
    sequences/sec on one chip (SURVEY §5 'BERT-base seq/s')."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretrain_loss)

    if on_tpu:
        # XLA attention, not the pallas flash path: measured faster at
        # S=128 on v5e (PERF.md §3 — scores fit on-chip at this size)
        cfg = BertConfig(attention_probs_dropout_prob=0.0,
                         hidden_dropout_prob=0.0,
                         max_position_embeddings=128)
        # bs sweep on v5e (PERF.md §7): 32/64/128/256 →
        # 1022/1270/1294/1172 seq/s — 128 is the knee
        batch, seq, iters = 128, 128, 20
    else:
        cfg = BertConfig.tiny()
        batch, seq, iters = 4, 32, 2

    with dygraph.guard():
        model = BertForPretraining(cfg)
        opt = fluid.optimizer.Adam(1e-4, parameter_list=model.parameters())

        def loss_fn(m, ids, tt, mlm, nsp):
            return pretrain_loss(m, ids, tt, mlm, nsp)

        step = TrainStep(model, loss_fn, opt,
                         amp_dtype=jnp.bfloat16 if on_tpu else None)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        tt = np.zeros((batch, seq), np.int64)
        mlm = np.where(rng.rand(batch, seq) < 0.15,
                       rng.randint(0, cfg.vocab_size, (batch, seq)),
                       -1).astype(np.int64)
        nsp = rng.randint(0, 2, (batch, 1)).astype(np.int64)

        jax.block_until_ready(step(ids, tt, mlm, nsp))
        t0 = time.perf_counter()
        for _ in range(iters):
            l = step(ids, tt, mlm, nsp)
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0

    seq_per_sec = batch * iters / dt
    # analytic train FLOPs/seq (fwd+bwd = 3× fwd, 2 FLOPs per MAC):
    #   block matmuls: 6 · 12·L·h²  per token  (QKVO 4h² + FFN 8h²)
    #   attention scores+context: 12·L·h·S per token (QKᵀ and PV, 2·S²·h
    #   each per layer fwd)
    #   MLM head: 6·h·V per token
    h, L, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    flops_per_seq = seq * (72.0 * L * h * h + 12.0 * L * h * seq
                           + 6.0 * h * V)
    return seq_per_sec, flops_per_seq


def bench_transformer_big(on_tpu):
    """Transformer-big WMT en-de train step (BASELINE.json config[3]):
    tokens/sec on one chip, bf16, fused step (the ParallelExecutor
    fused-allreduce path collapses to the single fused XLA program on one
    chip; multi-chip uses the same step dp-sharded)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.models.transformer import (Transformer,
                                               TransformerConfig,
                                               transformer_loss)

    if on_tpu:
        cfg = TransformerConfig.big(dropout=0.0, max_length=64)
        batch, seq, iters = 64, 64, 10
    else:
        cfg = TransformerConfig.tiny(dropout=0.0)
        batch, seq, iters = 2, 8, 2

    with dygraph.guard():
        model = Transformer(cfg)
        opt = fluid.optimizer.Adam(1e-4, parameter_list=model.parameters())

        def loss_fn(m, src, trg, lbl):
            logits = m(src, trg)
            return transformer_loss(logits, lbl)

        step = TrainStep(model, loss_fn, opt,
                         amp_dtype=jnp.bfloat16 if on_tpu else None)
        rng = np.random.RandomState(0)
        src = rng.randint(1, cfg.src_vocab_size, (batch, seq)).astype(np.int64)
        trg = rng.randint(1, cfg.trg_vocab_size, (batch, seq)).astype(np.int64)
        lbl = rng.randint(1, cfg.trg_vocab_size,
                          (batch, seq, 1)).astype(np.int64)

        jax.block_until_ready(step(src, trg, lbl))
        t0 = time.perf_counter()
        for _ in range(iters):
            l = step(src, trg, lbl)
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0

    tokens_per_sec = batch * 2 * seq * iters / dt  # src + trg tokens
    # analytic train FLOPs per token (2 FLOP/MAC, train = 3× fwd), averaged
    # over the src+trg token count; embedding lookups free, logits matmul
    # charged to trg tokens:
    d, di, L = cfg.d_model, cfg.d_inner, cfg.n_layer
    V = cfg.trg_vocab_size
    enc_lin = 2.0 * (4 * d * d + 2 * d * di)       # QKVO + FFN, per tok/layer
    dec_lin = 2.0 * (8 * d * d + 2 * d * di)       # + cross-attn QKVO
    attn = 4.0 * seq * d                           # QKᵀ + PV, per tok/layer
    fwd_per_pair = (L * (enc_lin + attn)           # encoder, src token
                    + L * (dec_lin + 2 * attn)     # decoder, trg token
                    + 2.0 * d * V)                 # output projection
    flops_per_tok = 3.0 * fwd_per_pair / 2.0       # per (src+trg)-avg token
    return tokens_per_sec, flops_per_tok


def bench_ernie(on_tpu):
    """ERNIE-base finetune step (BASELINE.json config[4]): AMP bf16 +
    gradient merge k=4 (the reference recipe), seq/sec on one chip."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.models.ernie import (ErnieConfig,
                                         ErnieForSequenceClassification)
    from paddle_tpu.dygraph.tape import dispatch_op

    if on_tpu:
        cfg = ErnieConfig.base(attention_probs_dropout_prob=0.0,
                               hidden_dropout_prob=0.0,
                               max_position_embeddings=128)
        batch, seq, iters = 64, 128, 16
    else:
        cfg = ErnieConfig(vocab_size=128, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=2,
                          intermediate_size=64, max_position_embeddings=32)
        batch, seq, iters = 4, 16, 4

    with dygraph.guard():
        model = ErnieForSequenceClassification(cfg, num_labels=2, dropout=0.0)
        opt = fluid.optimizer.Adam(5e-5, parameter_list=model.parameters())

        def loss_fn(m, ids, tt, y):
            logits = dispatch_op('cast', {'x': m(ids, tt)},
                                 {'dtype': 'float32'})
            l, _ = dispatch_op('softmax_with_cross_entropy',
                               {'logits': logits, 'label': y}, {})
            return dispatch_op('reduce_mean', {'x': l}, {})

        step = TrainStep(model, loss_fn, opt,
                         amp_dtype=jnp.bfloat16 if on_tpu else None,
                         accum_steps=4)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        tt = np.zeros((batch, seq), np.int64)
        y = rng.randint(0, 2, (batch, 1)).astype(np.int64)

        jax.block_until_ready(step(ids, tt, y))
        t0 = time.perf_counter()
        for _ in range(iters):
            l = step(ids, tt, y)
        jax.block_until_ready(l)
        dt = time.perf_counter() - t0

    seq_per_sec = batch * iters / dt
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    flops_per_seq = seq * (72.0 * L * h * h + 12.0 * L * h * seq)
    return seq_per_sec, flops_per_seq


def bench_dispatch_overhead(on_tpu):
    """Eager-tape step vs fused TrainStep on a ResNet bottleneck block and a
    BERT layer, with the per-op kernel cache off/on (slope-method timing —
    PERF.md §9). Measurable on CPU: the quantity under test is host-side
    dispatch, not FLOPs."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_dispatch import measure_all
    return measure_all(iters=8 if on_tpu else 4)


def bench_ir_passes(on_tpu):
    """Pass-pipeline front-end bench (PERF.md §10): jaxpr eqn count and
    trace+lower seconds pass-off vs pass-on (fuse knobs live) for the
    multi-param Adam MLP / ResNet block / BERT layer, plus the
    executor_compile_seconds cold/warm A/B. Valid on CPU: the quantity
    under test is host-side trace+lower, not FLOPs."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_passes import measure_all
    return measure_all(iters=3 if on_tpu else 2, smoke=not on_tpu)


def bench_verify_overhead(on_tpu):
    """Static-verifier cost (PERF.md §17): paddle_tpu/analysis/ at
    PADDLE_TPU_VERIFY=passes on the multi-param Adam MLP recipe — the
    verifier's fraction of the cold lower+compile it rides on (must be
    ≤2%) and the warm-step ratio (must be ~1.0: build-time only). Valid
    on CPU: the quantity under test is host-side analysis time."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_verify import measure_all
    return measure_all(iters=5 if on_tpu else 3, smoke=not on_tpu)


def bench_memory_plan(on_tpu):
    """Static memory-planner bench (PERF.md §20): plan latency as a
    fraction of the cold lower+compile it informs (≤1% acceptance) and
    the auto-remat memory-vs-steps/s tradeoff on an activation-heavy MLP
    (fits a simulated PADDLE_TPU_HBM_BUDGET_MB the unplanned program
    exceeds, bitwise losses). Valid on CPU: the quantities under test
    are host-side planning time and byte arithmetic."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_plan import measure_all
    return measure_all(smoke=not on_tpu, iters=7 if on_tpu else 5)


def bench_partitioner(on_tpu):
    """Unified SPMD partitioner bench (docs/PARTITIONER.md): per-Program
    spec-resolution time (zero tracing — the cost the Executor pays per
    compile-cache miss on a partitioned program), spec parity vs the
    retired per-module plumbing, and dp×fsdp / dp×tp SpmdTrainStep
    composition parity with the quantized-collective sync counters
    asserted. Runs in a SUBPROCESS: the composed meshes need ≥8 devices
    (XLA_FLAGS before backend init on CPU). Valid on CPU: the quantities
    under test are host-side resolution time + scheduling/shape
    discipline."""
    import subprocess
    # always on the CPU: this process holds the chip
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    flags = env.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)), 'tools',
                      'bench_partition.py')]
        + ([] if on_tpu else ['--smoke']),
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f'bench_partition failed: {r.stderr[-2000:]}')
    out = {}
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith('{'):
            d = json.loads(line)
            out[d['bench']] = d
    return out


def bench_sparse_section(on_tpu):
    """Sparse embedding fast path (PERF.md §21, docs/SPARSE.md): rows-only
    grad+update step vs the dense-scatter legacy at V=1e6 / nnz≈4k,
    lookups/sec, DP bytes-on-wire (dense all-reduce vs quantized COO
    push), and executor-spine sparse-vs-dense parity. Valid on CPU: the
    quantities are HBM-traffic asymmetry (O(V·D) vs O(nnz·D)) and byte
    accounting, not device-specific kernels."""
    import subprocess
    # always on the CPU: this process holds the chip
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)), 'tools',
                      'bench_sparse.py')],
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f'bench_sparse failed: {r.stderr[-2000:]}')
    out = {}
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith('{'):
            d = json.loads(line)
            key = d['bench']
            if key == 'sparse_step_time':
                key = f"sparse_step_time_v{d['vocab']}"
            out[key] = d
    return out


def bench_serving_batcher(on_tpu):
    """Serving-path load bench (PERF.md §11): closed-loop clients through
    the dynamic micro-batcher (paddle_tpu/serving/) vs serial single-request
    Predictor.run — throughput, p50/p99, padding waste, bitwise parity.
    Valid on CPU: the quantity under test is dispatch amortization."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_serving import measure_all
    return measure_all(smoke=not on_tpu)


def bench_decode_engine(on_tpu):
    """Stateful decode engine bench (PERF.md §13): uncached whole-sequence
    greedy vs the paged-KV continuous-batching engine vs drain-then-refill
    wave batching, on a heavy-tailed mixed-length workload — tokens/s,
    slot occupancy, prefill/decode split, bitwise token parity — plus the
    sampled-replay section (pinned request_ids run twice, bitwise) and
    speculative decoding vs lockstep (n-gram drafts, batched (S, k)
    verify). Valid on CPU: the quantity under test is scheduling + shape
    discipline."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_decode import measure_all
    return measure_all(smoke=not on_tpu)


def bench_serving_tier(on_tpu):
    """Serving-tier bench (PERF.md §19): open-loop Poisson p50/p99 through
    the multi-replica router (1 vs 2 replicas), prefix-cache hit rate +
    prefill-compute-saved on a shared-system-prompt workload, disaggregated
    handoff parity, and a zero-drop failover drill. Valid on CPU: routing,
    caching, and scheduling are the quantities under test."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_router import measure_all
    return measure_all(smoke=not on_tpu)


def bench_async_pipeline(on_tpu):
    """Async train-loop pipeline A/B (PERF.md §12): host-bound reader +
    compute-bound step, sync (per-step np.asarray) vs the K=2 in-flight
    FetchHandle window, plus the zero-copy staged-feed check. Valid on
    CPU: the quantity under test is host/device overlap, not FLOPs."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_pipeline import measure_all
    return measure_all(smoke=not on_tpu)


def bench_pipeline_parallel(on_tpu):
    """Pipeline-parallel schedules (PERF.md "Pipeline parallelism"):
    GPipe vs 1F1B at the same auto-cut — bitwise loss parity, predicted
    (staged planner) AND measured (XLA memory_analysis) peak residency,
    and auto-cut quality vs every manual cut on bert_layer. Valid on
    CPU: parity, planner-vs-XLA agreement and cut quality are
    host-independent; steps/s is trend-only."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_pp import measure_all
    return measure_all(smoke=not on_tpu)


def bench_resilience(on_tpu):
    """Checkpoint stall + restart lost-work (PERF.md §14) and self-healing
    (PERF.md §15): async checkpointing must add < 1 step of stall, the
    supervisor+watchdog must be ≤2% on the healthy path, and neither may
    ever perturb the losses. Valid on CPU: host/IO overlap under test."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_resilience import measure_all
    return measure_all(smoke=not on_tpu)


def bench_elastic(on_tpu):
    """Elastic runtime (ISSUE 19): the autoscaler's Poisson ramp drill
    (replica count follows load, zero drops through scale-up/drain, every
    decision recorded with its trigger) and the goodput resize-vs-crash
    bucket separation. Valid on CPU: control-loop and accounting
    behaviour are the quantities under test."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), 'tools'))
    from bench_elastic import measure_all
    return measure_all(smoke=not on_tpu)


def bench_collectives_section(on_tpu):
    """Quantized + bucketed gradient collectives (PERF.md §16). Runs in a
    SUBPROCESS: the 8-device virtual CPU mesh needs XLA_FLAGS set before
    backend init, which this process has already done. Valid on CPU: the
    headline number is telemetry-counted bytes-on-wire reduction (≥3.5×
    int8 acceptance), which is backend-independent."""
    import subprocess
    # always on the CPU: this process holds the chip
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    flags = env.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)), 'tools',
                      'bench_collectives.py')]
        + ([] if on_tpu else ['--smoke']),
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f'bench_collectives failed: {r.stderr[-2000:]}')
    out = {}
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith('{'):
            d = json.loads(line)
            out[d['bench']] = d
    return out


def bench_fleet_section(on_tpu):
    """Fleet weak scaling (PERF.md §18). Runs in a SUBPROCESS per fleet
    size: each worker is a REAL jax.distributed process (gloo CPU
    collectives) through the executor spine. Valid on CPU: the quantity
    under test is the fleet runtime's overhead against perfect
    timesharing (samples/s-normalized weak-scaling efficiency), which is
    the transferable number; acceptance ≥0.8 at nproc=2 for the
    compute-bound recipe."""
    import subprocess
    # always on the CPU: this process holds the chip
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)        # workers own one device each
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)), 'tools',
                      'bench_fleet.py'), '--nprocs', '1,2,4']
        + ([] if on_tpu else []),
        env=env, capture_output=True, text=True, timeout=1500)
    if r.returncode != 0:
        raise RuntimeError(f'bench_fleet failed: {r.stderr[-2000:]}')
    out = {}
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith('{'):
            d = json.loads(line)
            if d['bench'] == 'fleet_weak_scaling_summary':
                out = d
    return out


def bench_telemetry_sidecar(on_tpu):
    """Telemetry sidecar for the bench run: the headline benches above run
    with telemetry off (their numbers stay comparable across PRs), then the
    on-vs-off eager A/B from bench_dispatch runs here — its enabled half
    populates the metrics registry — and the registry dict export is written
    next to the BENCH_*.json evidence."""
    from bench_dispatch import measure_telemetry_overhead
    from paddle_tpu import observability as obs
    ab = measure_telemetry_overhead(iters=4 if on_tpu else 2, smoke=True)
    sidecar = {
        'telemetry_overhead': ab,
        'metrics': obs.registry.to_dict(),
    }
    out_dir = os.environ.get('PADDLE_TPU_METRICS_DIR') or os.getcwd()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, 'BENCH_telemetry.json')
    with open(path, 'w') as f:
        json.dump(sidecar, f, indent=1)
    return {'path': path, 'on_over_off': ab['on_over_off']}


def main():
    import jax
    from paddle_tpu.core.places import on_tpu as _on_tpu
    devices, backend = jax.devices(), jax.default_backend()
    on_tpu = _on_tpu()
    dev = devices[0]
    chip = getattr(dev, 'device_kind', str(dev))
    peak = chip_peak_tflops(dev) if on_tpu else None
    emit({"metric": "backend_init", "backend": backend, "chip": chip,
          "chip_peak_bf16_tflops": peak})

    failures = []
    summary = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": None, "unit": "images/sec/chip", "vs_baseline": None,
        "mfu": None, "bert_base_seq_per_sec": None, "bert_mfu": None,
        "chip": chip, "chip_peak_bf16_tflops": peak,
    }

    def run(name, fn):
        try:
            return fn()
        except Exception as e:  # print the section's own error, keep going
            traceback.print_exc(file=sys.stderr)
            emit({"metric": name, "error": f"{type(e).__name__}: {e}"})
            failures.append(name)
            return None

    r = run("resnet50_train_images_per_sec_per_chip",
            lambda: bench_resnet(on_tpu))
    if r is not None:
        mfu = (r * RESNET50_TRAIN_GFLOP_PER_IMG / 1e3 / peak) if peak \
            else None
        summary.update(value=round(r, 2),
                       vs_baseline=round(r / V100_BASELINE_IMG_S, 3),
                       mfu=round(mfu, 4) if mfu else None)
        emit({"metric": "resnet50_train_images_per_sec_per_chip",
              "value": summary["value"], "unit": "images/sec/chip",
              "vs_baseline": summary["vs_baseline"], "mfu": summary["mfu"]})

    b = run("bert_base_seq_per_sec", lambda: bench_bert(on_tpu))
    if b is not None:
        seq_s, flops_per_seq = b
        bert_mfu = (seq_s * flops_per_seq / 1e12 / peak) if peak else None
        summary.update(bert_base_seq_per_sec=round(seq_s, 2),
                       bert_mfu=round(bert_mfu, 4) if bert_mfu else None)
        emit({"metric": "bert_base_seq_per_sec",
              "value": summary["bert_base_seq_per_sec"], "unit": "seq/sec",
              "mfu": summary["bert_mfu"]})

    t = run("transformer_big_tokens_per_sec",
            lambda: bench_transformer_big(on_tpu))
    if t is not None:
        tok_s, flops_per_tok = t
        t_mfu = (tok_s * flops_per_tok / 1e12 / peak) if peak else None
        summary.update(transformer_big_tokens_per_sec=round(tok_s, 1),
                       transformer_big_mfu=round(t_mfu, 4) if t_mfu
                       else None)
        emit({"metric": "transformer_big_tokens_per_sec",
              "value": summary["transformer_big_tokens_per_sec"],
              "unit": "tokens/sec", "mfu": summary.get("transformer_big_mfu")})

    e = run("ernie_finetune_seq_per_sec", lambda: bench_ernie(on_tpu))
    if e is not None:
        seq_s, flops_per_seq = e
        e_mfu = (seq_s * flops_per_seq / 1e12 / peak) if peak else None
        summary.update(ernie_finetune_seq_per_sec=round(seq_s, 2),
                       ernie_mfu=round(e_mfu, 4) if e_mfu else None)
        emit({"metric": "ernie_finetune_seq_per_sec",
              "value": summary["ernie_finetune_seq_per_sec"],
              "unit": "seq/sec", "mfu": summary.get("ernie_mfu")})

    d = run("dygraph_eager_overhead", lambda: bench_dispatch_overhead(on_tpu))
    if d is not None:
        rb, bl = d['resnet_block'], d['bert_layer']
        emit({"metric": "dygraph_eager_overhead",
              "resnet_block": rb, "bert_layer": bl})
        summary.update(
            eager_cache_speedup_resnet_block=rb["cache_speedup"],
            eager_vs_fused_resnet_block=rb["eager_cached_vs_fused"])

    p = run("ir_pass_pipeline", lambda: bench_ir_passes(on_tpu))
    if p is not None:
        emit({"metric": "ir_pass_pipeline",
              "mlp_adam": p['mlp_adam'], "resnet_block": p['resnet_block'],
              "bert_layer": p['bert_layer'],
              "executor_compile": p['executor_compile']})
        summary.update(
            ir_pass_eqn_reduction_mlp_adam=p['mlp_adam']['eqn_reduction'],
            ir_pass_trace_lower_speedup_mlp_adam=(
                p['mlp_adam']['trace_lower_speedup']))

    sv = run("serving_batcher", lambda: bench_serving_batcher(on_tpu))
    if sv is not None:
        emit({"metric": "serving_batcher",
              "serial": sv['serial'], "batcher": sv['batcher'],
              "overload": sv['overload']})
        summary.update(
            serving_batcher_speedup=sv['batcher']['speedup_vs_serial'],
            serving_batcher_p99_ms=sv['batcher']['p99_ms'])

    de = run("decode_engine", lambda: bench_decode_engine(on_tpu))
    if de is not None:
        emit({"metric": "decode_engine",
              "uncached": de['uncached'], "continuous": de['continuous'],
              "drain": de['drain'], "sampled": de['sampled'],
              "speculative": de['speculative'],
              "kv_quant": de['kv_quant']})
        summary.update(
            decode_continuous_vs_drain=de['continuous']['speedup_vs_drain'],
            decode_tokens_per_s=de['continuous']['tokens_per_s'],
            decode_bitwise=de['continuous']['bitwise_equal'])
        summary.update(
            spec_decode_vs_lockstep=de['speculative']['speedup_vs_lockstep'],
            spec_decode_acceptance=de['speculative']['acceptance'],
            spec_decode_bitwise=de['speculative']['bitwise_equal'],
            decode_sampled_replayable=de['sampled']['replayable'])
        kv = de['kv_quant']
        summary.update(
            kv_quant_hbm_bytes_f32_over_int8=kv['hbm_bytes_f32_over_int8'],
            kv_quant_int8_match_rate=(
                kv['per_dtype']['int8']['match_rate_vs_f32']),
            kv_quant_f32_bitwise=kv['per_dtype']['f32']['bitwise_equal'],
            kv_quant_int8_slots_per_chip=kv['slots_per_chip']['int8'])

    st = run("serving_tier", lambda: bench_serving_tier(on_tpu))
    if st is not None:
        emit({"metric": "serving_tier",
              "scaling": st['scaling'], "prefix_cache": st['prefix_cache'],
              "disagg": st['disagg'], "failover": st['failover']})
        summary.update(
            serving_tier_hit_rate=st['prefix_cache']['cache_on']['hit_rate'],
            serving_tier_prefill_tokens_saved=(
                st['prefix_cache']['cache_on']['prefill_tokens_saved']),
            serving_tier_cache_speedup=st['prefix_cache']['speedup'],
            serving_tier_failover_dropped=st['failover']['dropped'],
            serving_tier_bitwise=(
                st['prefix_cache']['cache_on']['bitwise_equal']
                and st['disagg']['bitwise_equal']))

    pl = run("async_pipeline", lambda: bench_async_pipeline(on_tpu))
    if pl is not None:
        emit({"metric": "async_pipeline",
              "async_pipeline": pl['async_pipeline'],
              "staged_feeds": pl['staged_feeds']})
        summary.update(
            async_pipeline_speedup=pl['async_pipeline']['speedup'],
            async_pipeline_bitwise=pl['async_pipeline']
            ['bitwise_identical'])

    pp = run("pipeline_parallel", lambda: bench_pipeline_parallel(on_tpu))
    if pp is not None:
        emit({"metric": "pipeline_parallel",
              "schedules": pp['schedules'], "autocut": pp['autocut']})
        summary.update(
            pp_bitwise=pp['schedules']['bitwise_identical'],
            pp_1f1b_peak_le_gpipe=(
                pp['schedules']['predicted_1f1b_le_gpipe']
                and pp['schedules']['measured_1f1b_le_gpipe']),
            pp_autocut_within_tolerance=pp['autocut']
            ['within_tolerance'])

    rz = run("resilience", lambda: bench_resilience(on_tpu))
    if rz is not None:
        emit({"metric": "resilience",
              "stall": rz['resilience_stall'],
              "restart": rz['resilience_restart'],
              "supervised": rz['resilience_supervised'],
              "nan_recovery": rz['resilience_nan_recovery']})
        summary.update(
            ckpt_stall_steps=rz['resilience_stall']['async_stall_steps'],
            ckpt_bitwise=rz['resilience_stall']['bitwise_identical'],
            supervisor_overhead_frac=rz['resilience_supervised']
            ['overhead_frac'],
            supervisor_bitwise=rz['resilience_supervised']
            ['bitwise_identical'],
            nan_recovery_ok=rz['resilience_nan_recovery']['recovered'])

    el = run("elastic", lambda: bench_elastic(on_tpu))
    if el is not None:
        emit({"metric": "elastic",
              "autoscale_ramp": el['elastic_autoscale_ramp'],
              "resize_accounting": el['elastic_resize_accounting']})
        summary.update(
            elastic_autoscale_dropped=el['elastic_autoscale_ramp']
            ['dropped'],
            elastic_autoscale_bitwise=el['elastic_autoscale_ramp']
            ['bitwise_equal'],
            elastic_max_replicas_seen=el['elastic_autoscale_ramp']
            ['max_replicas_seen'],
            elastic_resize_buckets_separate=el['elastic_resize_accounting']
            ['buckets_separate'])

    co = run("collectives", lambda: bench_collectives_section(on_tpu))
    if co is not None:
        emit({"metric": "collectives",
              "bytes": co['collectives_bytes'],
              "steps": co['collectives_steps'],
              "convergence": co['collectives_convergence'],
              "bucketing": co['collectives_bucketing']})
        summary.update(
            collective_bytes_reduction_int8=co['collectives_bytes']
            ['bytes_reduction_int8'],
            collective_convergence_parity=co['collectives_convergence']
            ['parity'],
            collective_bucketing_bitwise=co['collectives_bucketing']
            ['bitwise_identical'])

    vo = run("verify_overhead", lambda: bench_verify_overhead(on_tpu))
    if vo is not None:
        emit({"metric": "verify_overhead",
              "overhead": vo['verify_overhead'],
              "pipeline_ab": vo['verify_pipeline_ab']})
        summary.update(
            verify_frac_of_compile=vo['verify_overhead']
            ['verify_frac_of_compile'],
            verify_warm_step_ratio=vo['verify_overhead']
            ['warm_step_ratio'])

    mp = run("memory_plan", lambda: bench_memory_plan(on_tpu))
    if mp is not None:
        emit({"metric": "memory_plan",
              "latency": mp['plan_latency'], "remat": mp['plan_remat']})
        summary.update(
            plan_frac_of_compile=mp['plan_latency']
            ['plan_frac_of_compile'],
            auto_remat_fits_budget=mp['plan_remat']['fits_budget'],
            auto_remat_bitwise=mp['plan_remat']['bitwise_identical'])

    pt = run("partitioner", lambda: bench_partitioner(on_tpu))
    if pt is not None:
        emit({"metric": "partitioner",
              "spec_resolution": pt['partition_spec_resolution'],
              "parity": pt['partition_parity'],
              "composition": pt['partition_composition']})
        summary.update(
            partition_resolve_s=pt['partition_spec_resolution']
            ['resolve_s'],
            partition_parity_ok=pt['partition_parity']['ok'],
            partition_composition_ok=pt['partition_composition']['ok'])

    fw = run("fleet_runtime", lambda: bench_fleet_section(on_tpu))
    if fw is not None:
        emit({"metric": "fleet_runtime",
              "steps_per_s": fw.get('steps_per_s'),
              "samples_per_s": fw.get('samples_per_s'),
              "efficiency": fw.get('efficiency')})
        summary.update(
            fleet_efficiency_nproc2=fw.get('efficiency_nproc2'),
            fleet_acceptance_ge_0_8=fw.get('acceptance_ge_0_8'))

    se = run("sparse_embedding", lambda: bench_sparse_section(on_tpu))
    if se is not None:
        big = se.get('sparse_step_time_v1000000', {})
        wire = se.get('sparse_bytes_on_wire', {})
        emit({"metric": "sparse_embedding",
              "step_time": big,
              "lookup": se.get('sparse_lookup_throughput'),
              "bytes_on_wire": wire,
              "executor_parity": se.get('sparse_executor_parity')})
        summary.update(
            sparse_over_dense_v1e6=big.get('sparse_over_dense'),
            sparse_dense_over_int8_bytes=wire.get('dense_over_sparse_int8'),
            sparse_f32_over_int8_bytes=wire.get('sparse_f32_over_int8'),
            sparse_parity_ok=se.get('sparse_executor_parity',
                                    {}).get('ok'))

    s = run("telemetry_sidecar", lambda: bench_telemetry_sidecar(on_tpu))
    if s is not None:
        emit({"metric": "telemetry_sidecar", "path": s["path"],
              "telemetry_on_over_off": s["on_over_off"]})

    emit(summary)  # last line: the original ONE-JSON-line driver contract
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
