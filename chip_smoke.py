"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # from the repo root, on a machine with a TPU

One process, no children, no network; weights and data are made from seeds.
It drives the main paths once through the entry points a user calls, at the
full width of models the repo supports, and checks what comes out by the
repo's own means:

  device     platform / device_kind / count and the jax, jaxlib, libtpu
             versions; anything but a TPU backend exits non-zero
  train      ResNet-50 224x224 NHWC bs128, bf16 compute over f32 masters,
             Momentum, dygraph.guard() + dygraph.jit.TrainStep
  serve      TransformerLM(CausalLMConfig()) behind build_replica_stack +
             ServingServer: 16 concurrent POST /generate, one streamed, one
             replayed by request_id; logits vs the uncached forward
  kernels    fused_attention where its pallas kernel applies, and
             paged_attention's decode read at the head sizes, row paddings
             and pool dtypes no served cell has, against plain references
  group_read the grouped read's pallas kernel at the four served callers'
             shapes, one Mosaic call under each caller's scope, against
             the XLA walk
  static     models.lenet.build_static_lenet under
             fluid.Executor(fluid.TPUPlace(0)), fed from the DataLoader ring
  train_dp4  only with >= 4 devices: BERT-base S=128, 128 sequences per chip,
             fleet.init(mesh_shape={'dp': 4}) + TrainStep(data_sharding=...)

A phase that raises ends the run non-zero at once. The last line of stdout is
one JSON object with exactly two keys,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}, the
device as JAX reports it; the driver parses that line and refuses any other
key. The line before it, "[summary] {...}", carries each phase's figures and
ends with "claim": null. The compile seconds and ms/step are informational:
no rate or utilisation is computed, so no peak is assumed.

The phases are plain functions that take their sizes as arguments;
tests/framework/test_chip_smoke.py calls the same code at tiny sizes on the
CPU. Nothing in here switches the device check off.
"""
from __future__ import annotations

import copy
import gc
import http.client
import importlib.metadata
import itertools
import json
import math
import sys
import threading
import time

import numpy as np


def say(phase, text):
    print(f'[{phase}] {text}', flush=True)


# -- compile accounting ------------------------------------------------------

class CompileCounter:
    """Counts jax's own compile events through the public jax.monitoring
    hooks: every executable XLA builds or loads ('compiles'), and the
    persistent cache's requests / hits / writes. The repo's telemetry
    (train_step/build span, compile_cache_* and persistent_cache_* counters,
    the eager kernel-cache stats) is read beside it, where it exists."""

    BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
    EVENTS = {
        '/jax/compilation_cache/compile_requests_use_cache': 'requests',
        '/jax/compilation_cache/cache_hits': 'hits',
        '/jax/compilation_cache/cache_misses': 'writes',
    }

    def __init__(self):
        from jax import monitoring
        self.counts = {'compiles': 0, 'compile_secs': 0.0, 'requests': 0,
                       'hits': 0, 'writes': 0}
        self._lock = threading.Lock()
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **kw):
        key = self.EVENTS.get(event)
        if key is not None:
            with self._lock:
                self.counts[key] += 1

    def _on_duration(self, event, duration, **kw):
        if event == self.BACKEND_COMPILE:
            with self._lock:
                self.counts['compiles'] += 1
                self.counts['compile_secs'] += duration

    def snapshot(self):
        with self._lock:
            return dict(self.counts)

    def since(self, before):
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


# -- device ------------------------------------------------------------------

def device_phase():
    import jax
    import jaxlib
    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version('libtpu')
    except importlib.metadata.PackageNotFoundError:
        libtpu = 'not installed'
    info = {'platform': dev.platform, 'kind': dev.device_kind,
            'count': len(jax.devices())}
    say('device', f"platform={dev.platform} device_kind={dev.device_kind!r} "
                  f"count={info['count']} jax={jax.__version__} "
                  f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return info


def _metric_total(metrics, name):
    """Sum of a counter's samples in an observability registry export."""
    return int(sum(s['value']
                   for s in metrics.get(name, {}).get('samples', [])))


# -- train -------------------------------------------------------------------

def train_phase(counter, batch=128, image=224, steps=8, lr=0.01, seed=0):
    """ResNet-50 NHWC, bf16 compute / f32 masters, Momentum, one fixed seeded
    batch through dygraph.guard() + TrainStep (the shape of
    examples/train_resnet_dygraph.py, BASELINE config[1]).

    lr is 0.01, not the example's 0.1: on ONE repeated batch 0.1 overshoots
    for its first ~8 steps (loss 7.1 -> 39 -> 7.2 in ten steps on the CPU at
    64x64), and this phase checks that the optimizer descends, not a
    schedule."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu import observability as obs
    from paddle_tpu.core.random import seed as set_seed
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.dygraph.tape import dispatch_op
    from paddle_tpu.models import ResNet50

    assert steps >= 3
    with dygraph.guard(), obs.telemetry_guard(True):
        obs.reset()
        set_seed(seed)
        model = ResNet50(class_dim=1000, data_format='NHWC')
        opt = fluid.optimizer.Momentum(lr, momentum=0.9,
                                       parameter_list=model.parameters())

        def loss_fn(m, x, y):
            logits = dispatch_op('cast', {'x': m(x)}, {'dtype': 'float32'})
            l, _ = dispatch_op('softmax_with_cross_entropy',
                               {'logits': logits, 'label': y}, {})
            return dispatch_op('reduce_mean', {'x': l}, {})

        step = TrainStep(model, loss_fn, opt, amp_dtype=jnp.bfloat16)
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(batch, image, image, 3), jnp.bfloat16)
        y = rng.randint(0, 1000, (batch, 1)).astype(np.int64)
        params = dict(model.named_parameters())

        def stepped():
            """One step; True when every pre-step parameter buffer was
            deleted by it (donation is real on this backend)."""
            before = [p.value for p in params.values()]
            loss = jax.block_until_ready(step(x, y))
            return loss, all(b.is_deleted() for b in before)

        c0 = counter.snapshot()
        t0 = time.perf_counter()
        first, donated_first = stepped()
        first_s = time.perf_counter() - t0
        c1 = counter.snapshot()
        second, donated = stepped()
        losses = [first, second]
        t0 = time.perf_counter()
        for _ in range(steps - 2):
            losses.append(step(x, y))
        jax.block_until_ready(losses[-1])
        warm_ms = (time.perf_counter() - t0) / (steps - 2) * 1e3
        after_first = counter.since(c1)
        losses = [float(l) for l in losses]
        builds = sum(e['name'] == 'train_step/build'
                     for e in obs.tracer.snapshot()['traceEvents'])
        metrics = obs.registry.to_dict()
        cache = {k: _metric_total(metrics, k)
                 for k in ('persistent_cache_hits', 'persistent_cache_misses')}

        assert all(math.isfinite(l) for l in losses), losses
        ln1000 = math.log(1000.0)
        assert abs(losses[0] - ln1000) <= 0.05 * ln1000, \
            f'first loss {losses[0]:.4f} is not within 5% of ln 1000'
        assert losses[-1] < losses[0], f'loss did not fall: {losses}'
        # exactly one compile of the step: built once (the repo's span), one
        # executable in the jit's cache, and not one XLA compile after step 1
        assert builds == 1, f'train_step/build spans: {builds}'
        assert step._jitted._cache_size() == 1, step._jitted._cache_size()
        assert after_first['compiles'] == 0, \
            f'compiles after the first step: {after_first}'
        assert donated_first and donated, 'pre-step buffers survived the step'
        devs = {d for p in params.values() for d in p.value.devices()}
        assert devs <= set(jax.devices()), devs
        plats = {d.platform for d in devs}
        first_compile = counter.since(c0)['compile_secs'] \
            - after_first['compile_secs']

    say('train', f"ok ResNet-50 NHWC {image}x{image} bs{batch} bf16/f32 "
                 f"Momentum lr={lr} steps={steps} "
                 f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
                 f"(ln 1000 = {ln1000:.4f}); step compiled once "
                 f"(build spans {builds}, jit cache 1, 0 compiles after step "
                 f"1); {len(params)} params on {sorted(plats)}, pre-step "
                 f"buffers deleted; persistent cache hits "
                 f"{cache['persistent_cache_hits']} writes "
                 f"{cache['persistent_cache_misses']}")
    say('train', f"informational: first step {first_s:.1f} s (XLA compile "
                 f"{first_compile:.1f} s), warm wall {warm_ms:.1f} ms/step")
    return {'losses': [round(l, 4) for l in losses],
            'first_step_s': round(first_s, 2),
            'xla_compile_s': round(first_compile, 2),
            'warm_ms_per_step': round(warm_ms, 2),
            'persistent_cache': cache}


# -- serve -------------------------------------------------------------------

def _post(port, body, timeout=300):
    conn = http.client.HTTPConnection('127.0.0.1', port, timeout=timeout)
    try:
        conn.request('POST', '/generate', json.dumps(body),
                     {'Content-Type': 'application/json'})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _logit_rows(engine, model, prompt, pad_len):
    """(cached prefill row, cached decode row, uncached rows) for one prompt:
    the prefill's last row and one decode step through the engine's own
    phases, and the same two rows of the uncached whole-sequence forward.
    Also holds the step's pick to the device's: the ids are numpy's argmax
    of the rows of the same call, in every slot, and a greedy step copies
    its S ids to the host and nothing else."""
    from paddle_tpu.dygraph.tape import Tensor, no_grad_guard
    from paddle_tpu.serving.metrics import decode_logits_bytes_copied
    P = len(prompt)
    grabbed = []

    def grab(row):
        grabbed.append(np.array(row))
        return int(row.argmax())

    table = engine.reserve_table(P, 2)
    tok = engine.prefill(prompt, table, sampler=grab)
    tokens = [tok] + [None] * (engine.slots - 1)
    tables = [table] + [None] * (engine.slots - 1)
    ids, rows = engine.decode_step(tokens, tables, return_rows=True)
    assert np.array_equal(ids, rows.argmax(-1)), (ids, rows.argmax(-1))
    copied = decode_logits_bytes_copied.value
    engine.decode_step([int(ids[0])] + tokens[1:], tables)
    copied = decode_logits_bytes_copied.value - copied
    assert copied == engine.slots * 4, (copied, engine.slots)
    engine.release_table(table)
    buf = np.zeros((1, pad_len), np.int64)
    buf[0, :P] = prompt
    buf[0, P] = tok
    with no_grad_guard():
        ref = np.asarray(model(Tensor(buf, stop_gradient=True)).numpy())[0]
    return grabbed[0], np.array(rows[0]), ref[P - 1], ref[P]


def serve_phase(counter, cfg=None, slots=16, block_size=16, max_blocks=512,
                max_prompt_len=128, max_new_tokens_cap=192,
                prompt_lens=(32, 40, 48, 56, 64, 72, 80, 88, 96, 104, 112,
                             116, 120, 124, 126, 128),
                new_tokens=32, logit_tol=2e-2, seed=1234):
    """TransformerLM(cfg) (default: the class defaults, h=512, 6 layers, 8
    heads of 64, V=32,000) through build_replica_stack + ServingServer on a
    thread of this process — the objects the replica CLI builds. The block
    tables (16 slots of 20 blocks) hold more than one chunk of the step's
    read, so that "no array over every slot's padded context" says
    something of it (`DecodeEngine.step_context_arrays`).

    logit_tol bounds max|cached - uncached| / max|uncached|. On a TPU it
    cannot be array_equal: f32 matmuls run as one bf16 pass by default
    (measured 2.3e-3 of the output scale on a v5e, PERF.md "Bring-up"), and
    the paged and dense reads are different kernels at different shapes, so
    two correct paths differ by a few bf16 roundings through six layers.
    2e-2 is about five bf16 epsilons (2^-8): an order above that noise and
    far below any masking, scaling or position error, which move logits by
    their own scale."""
    import jax

    from paddle_tpu import dygraph, profiler
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    from paddle_tpu.ops.nn_ops import flash_kernel_applies
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.tier.replica import build_replica_stack

    assert len(prompt_lens) == slots, 'one request per slot keeps all busy'
    cfg = cfg or CausalLMConfig()
    V = cfg.vocab_size
    with dygraph.guard():
        default_generator.seed(seed)
        model = TransformerLM(cfg)
        model.eval()
        engine, scheduler, _ = build_replica_stack(
            model=model, slots=slots, block_size=block_size,
            max_blocks=max_blocks, max_prompt_len=max_prompt_len,
            max_new_tokens_cap=max_new_tokens_cap, prefix_cache=False,
            disagg=False, spec_decode=False)
        assert engine.pool.kv_dtype == 'f32'
        srv = ServingServer(None, host='127.0.0.1', port=0,
                            generator=scheduler, request_timeout=300.0)
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        timings = engine.warmup()
        warm_s = time.perf_counter() - t0
        warm_compiles = counter.since(c0)
        assert engine.warmed
        # one program per prefill rung and one lockstep step
        programs = engine.compiled_programs()
        assert programs == len(engine.prompt_buckets) + 1, programs
        srv.start()
        try:
            rng = np.random.RandomState(seed)
            prompts = [rng.randint(1, V, n).tolist() for n in prompt_lens]
            bodies = [{'prompt': p, 'max_new_tokens': new_tokens,
                       'stream': False} for p in prompts]
            bodies[0]['stream'] = True
            # the replayed request is SAMPLED: its stream is seeded by the
            # request_id, so the replay exercises the sampler too
            bodies[1].update(request_id='chip-smoke-replay',
                             temperature=0.7, top_k=40)
            profiler.reset_eager_kernel_cache_stats()
            c1 = counter.snapshot()
            replies = [None] * len(bodies)

            def client(i):
                replies[i] = _post(srv.port, bodies[i])

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(bodies))]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            traffic_s = time.perf_counter() - t0
            assert not any(t.is_alive() for t in threads), 'client hung'
            replay = _post(srv.port, bodies[1])
            after_warm = counter.since(c1)
            kstats = profiler.eager_kernel_cache_stats()

            token_lists = []
            for i, (status, text) in enumerate(replies + [replay]):
                assert status == 200, (i, status, text[:500])
                if i == 0:          # NDJSON stream: token lines, then done
                    lines = [json.loads(ln) for ln in text.splitlines()]
                    assert lines[-1].get('done') is True, lines[-1]
                    toks = lines[-1]['tokens']
                    assert [ln['token'] for ln in lines[:-1]] == toks
                else:
                    toks = json.loads(text)['tokens']
                assert len(toks) == new_tokens, (i, len(toks))
                assert all(0 <= t < V for t in toks), (i, toks)
                token_lists.append(toks)
            assert token_lists[1] == token_lists[-1], \
                'replay by request_id differs'
            # no compile after warm-up: by the engine's own count of its
            # programs and by jax's (executables built or loaded); and every
            # engine call was one of those programs: the eager per-op kernel
            # cache saw no lookup at all
            assert engine.compiled_programs() == programs
            assert after_warm['compiles'] == 0, \
                f'compiles after warm-up: {after_warm}'
            assert (kstats['hits'], kstats['misses']) == (0, 0), kstats

            # logits, not tokens (the scheduler is idle again: the engine is
            # single-threaded by design): the shortest prompt, on a low rung
            # of the ladder, and the longest, on the top rung
            logit_err = {}
            for prompt in (prompts[0], prompts[-1]):
                pf, dec, ref_pf, ref_dec = _logit_rows(
                    engine, model, prompt, engine.padded_context)
                assert pf.shape == dec.shape == (V,)
                assert np.isfinite(pf).all() and np.isfinite(dec).all()
                scale = float(np.abs(np.stack([ref_pf, ref_dec])).max())
                errs = (float(np.abs(pf - ref_pf).max()) / scale,
                        float(np.abs(dec - ref_dec).max()) / scale)
                assert max(errs) <= logit_tol, (len(prompt), errs, logit_tol)
                logit_err[len(prompt)] = {'prefill': errs[0],
                                          'decode': errs[1],
                                          'max_abs_logit': scale}
        finally:
            srv.shutdown(drain=False)

        # the pool lies as the paged writes and the read take it: no
        # compiled program copies or transposes an array of its size (the
        # lockstep step and the ladder's top rung, as compiled for this
        # device; serving/decode/kv_cache.py says why)
        pool_moves = {'step': engine.pool_moves(),
                      f'prefill_{engine.prompt_buckets[-1]}':
                          engine.pool_moves(engine.prompt_buckets[-1])}
        assert not any(pool_moves.values()), \
            f'programs move the K/V pool: {pool_moves}'
        # ... and the step's read builds no array over every slot's whole
        # padded context: it walks the live blocks a chunk at a time
        # (ops/nn_ops.py::paged_attention), on the one step executable of
        # the warm-up at every live-block count the traffic above had
        context_arrays = engine.step_context_arrays()
        assert not context_arrays, \
            f'the step holds per-slot dense contexts: {context_arrays}'

        # which attention path each rung took: the ops' own predicates, at
        # the shapes the engine dispatched
        k_pages = engine.pool.pages(0)[0]
        heads, d_head = engine.pool.heads[0]
        shaped = jax.ShapeDtypeStruct
        prefill_paths = {}
        for b in engine.prompt_buckets:
            qkv = shaped((1, heads, b, d_head), np.float32)
            prefill_paths[b] = 'pallas flash' \
                if flash_kernel_applies(qkv, qkv) else 'XLA gather'

    say('serve', f"ok TransformerLM h={cfg.hidden_size} "
                 f"L={cfg.num_hidden_layers} heads={heads}x{d_head} V={V}; "
                 f"slots={slots} block={block_size} pool=f32 "
                 f"ladder={engine.prompt_buckets}; {len(bodies)} concurrent "
                 f"POST /generate (prompts {min(prompt_lens)}-"
                 f"{max(prompt_lens)}, {new_tokens} new) + 1 replay: all "
                 f"200, token counts and ids ok, stream ok, sampled replay "
                 f"identical; {programs} engine programs; after warm-up: "
                 f"XLA compiles {after_warm['compiles']}, eager kernel-cache "
                 f"lookups {kstats['hits'] + kstats['misses']}; pool "
                 f"{'x'.join(map(str, k_pages.shape))} a layer's K or V, "
                 f"pool-sized copies in the compiled "
                 + ', '.join(f'{name}: {len(found)}'
                             for name, found in pool_moves.items())
                 + f"; arrays over {slots} slots' padded context in the "
                 f"step: {len(context_arrays)}")
    say('serve', f"logits vs uncached forward at pad {engine.padded_context}"
                 f", as a share of max|logit| (tolerance {logit_tol:g}): "
                 + '; '.join(
                     f"prompt {n}: prefill row {e['prefill']:.2e}, decode "
                     f"row {e['decode']:.2e} (max|logit| "
                     f"{e['max_abs_logit']:.3f})"
                     for n, e in logit_err.items()))
    say('serve', 'paged_prefill_attention path by rung: ' + ', '.join(
        f'{b}:{p}' for b, p in prefill_paths.items()))
    say('serve', f"informational: warm-up {warm_s:.1f} s "
                 f"({warm_compiles['compiles']} XLA compiles, "
                 f"{warm_compiles['compile_secs']:.1f} s in XLA; persistent "
                 f"cache requests {warm_compiles['requests']} hits "
                 f"{warm_compiles['hits']} writes {warm_compiles['writes']}); "
                 f"{len(bodies)} requests in {traffic_s:.1f} s wall")
    return {'warmup_s': round(warm_s, 2), 'warmup': warm_compiles,
            'warmup_phases': {k: round(v, 2) for k, v in timings.items()},
            'logit_err': logit_err,
            'pool_moves': {k: len(v) for k, v in pool_moves.items()},
            'step_context_arrays': len(context_arrays),
            'prefill_paths': prefill_paths}


# -- kernels -----------------------------------------------------------------

def experts_check(experts, calls, tol, seed=11):
    """`moe_experts` at the routed cells' sizes (no routed model is served
    here): ``experts`` (E, h, f) in bf16, and per ``calls`` (tokens, top_k)
    the op under the caller's scope `moe/experts`, compiled for this
    backend and run, against a per-expert float32 numpy loop at ``tol`` of
    the output scale (bf16 operands, the hidden activations and the result
    rounded to bf16). Where the op's predicate holds (a TPU) the compiled
    program must hold no `ragged-dot` custom call and two Mosaic custom
    calls, both with the scope in their `op_name` (ops/pallas_moe.py: gate
    and up in one pass, then down). Returns (path, {call: error})."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import llm_ops
    from paddle_tpu.ops.pallas_moe import kernel_op_names

    n_experts, h, f = experts
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    gate, up, down = (
        (jax.random.normal(key, shape, jnp.float32) * 0.05).astype(
            jnp.bfloat16)
        for key, shape in zip(keys, ((n_experts, h, f), (n_experts, h, f),
                                     (n_experts, f, h))))
    gate32, up32, down32 = (np.asarray(w, np.float32)
                            for w in (gate, up, down))

    # as a model's forward reaches the op: a jit of its own (every dispatch
    # is one) under the caller's scope, inside the program's jit. Bound in
    # the outermost jit itself, a pallas_call is lowered under the bare name
    # `pallas_call` once core/compile_cache.py has turned jax's full
    # tracebacks in locations off (PERF.md section 6, PR 33)
    op = jax.jit(llm_ops.moe_experts)

    def scoped(*args):
        with jax.named_scope('moe/experts'):
            return op(*args)

    rng = np.random.RandomState(seed)
    kernel = llm_ops.experts_kernel_applies(gate, gate)
    errs = {}
    for tokens, top_k in calls:
        x = jnp.asarray(rng.randn(tokens, h), jnp.bfloat16)
        # a router's imbalance: some experts drawn several times as often
        p = np.exp(0.45 * rng.randn(n_experts))
        ids = np.stack([rng.choice(n_experts, top_k, replace=False,
                                   p=p / p.sum()) for _ in range(tokens)])
        weights = rng.rand(tokens, top_k).astype('float32')
        args = (x, jnp.asarray(ids, jnp.int32), jnp.asarray(weights), gate,
                up, down)
        compiled = jax.jit(scoped).lower(*args).compile()
        if kernel:
            text = compiled.as_text()
            assert 'ragged-dot' not in text and 'ragged_dot' not in text
            names = kernel_op_names(text)
            assert len(names) == 2 and all('/moe/experts/' in n
                                           for n in names), names
        out, counts = compiled(*args)
        counts = np.asarray(counts)
        assert counts.tolist() == np.bincount(
            ids.ravel(), minlength=n_experts).tolist()
        x32 = np.asarray(x, np.float32)
        want = np.zeros((tokens, h), np.float32)
        for e in np.flatnonzero(counts):
            rows, slot = np.nonzero(ids == e)
            g, u = x32[rows] @ gate32[e], x32[rows] @ up32[e]
            want[rows] += weights[rows, slot][:, None] * (
                (g / (1.0 + np.exp(-g)) * u) @ down32[e])
        got = np.asarray(out, np.float32)
        assert got.shape == (tokens, h) and np.isfinite(got).all()
        err = float(np.abs(got - want).max() / np.abs(want).max())
        assert err <= tol, (tokens, top_k, err, tol)
        errs[f'{tokens}x{top_k}'] = err
    return 'pallas grouped matmul' if kernel else 'ragged_dot', errs


def kernels_phase(fused_shape=(8, 12, 512, 64), paged_slots=32,
                  block_size=16, pages_per_seq=12, num_blocks=512,
                  paged_cases=((4, 128, 'f32'), (12, 64, 'bf16'),
                               (12, 64, 'int8'), (5, 64, 'f32')),
                  experts=(128, 2048, 768),
                  expert_calls=((128, 6), (512, 8), (1024, 6)),
                  tol=2e-2, paged_tol=1e-4):
    """The kernels and attention paths the served model does not reach.

    `moe_experts` at the two routed cells' decode steps (128 tokens of
    top-6, 512 of top-8) and a prefill rung's 6,144 assignments, over 128
    experts of 2,048 × 768: :func:`experts_check`.

    `fused_attention` is off TransformerLM's path (use_fused_attention is
    False): one bf16 forward+backward through dispatch_op compiles its TPU
    branch, checked against plain jax.numpy at `tol` of the output scale
    (bf16 inputs: a few 2^-8 roundings).

    `paged_attention`'s single-query read (one formulation, the walk over
    the batch's live blocks) is served at 12 heads of 64 over an f32 pool;
    `paged_cases` (heads, head_dim, pool dtype) run it where no cell does:
    head_dim 128, bf16 and int8 pools, and 5 heads of 64, a 320-wide row in
    384 lanes. Each over tables of more than one chunk of blocks, half the
    slots at the full context, against a float64 numpy walk of the stored
    values at `paged_tol` of the output scale: the read sums in float32
    (its matmuls at precision HIGHEST); one bf16 pass would show 2e-3."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import dygraph
    from paddle_tpu.dygraph.tape import Tensor, dispatch_op
    from paddle_tpu.ops.nn_ops import flash_kernel_applies
    from paddle_tpu.serving.decode.kv_cache import row_lanes

    rng = np.random.RandomState(7)
    B, H, S, D = fused_shape
    scale = 1.0 / math.sqrt(D)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
               for _ in range(3))

    def ref_attn(q, k, v):
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s,
                      jnp.finfo(s.dtype).min)
        return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)

    with dygraph.guard():
        tq, tk, tv = (Tensor(a, stop_gradient=False) for a in (q, k, v))
        out = dispatch_op('fused_attention', {'q': tq, 'k': tk, 'v': tv},
                          {'sm_scale': scale, 'causal': True})
        loss = dispatch_op('reduce_sum', {'x': dispatch_op(
            'cast', {'x': out}, {'dtype': 'float32'})}, {})
        loss.backward()
        grads = [np.asarray(t.gradient(), np.float32) for t in (tq, tk, tv)]
    want = np.asarray(ref_attn(q, k, v), np.float32)
    want_g = jax.grad(lambda q, k, v: ref_attn(q, k, v)
                      .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    got = np.asarray(out.numpy(), np.float32)
    assert got.shape == (B, H, S, D) and np.isfinite(got).all()
    err = float(np.abs(got - want).max() / np.abs(want).max())
    gerr = max(float(np.abs(g - np.asarray(w, np.float32)).max()
                     / np.abs(np.asarray(w, np.float32)).max())
               for g, w in zip(grads, want_g))
    assert err <= tol and gerr <= tol, (err, gerr, tol)
    fused_path = 'pallas flash' if flash_kernel_applies(q, k) else 'XLA'
    say('kernels', f"ok fused_attention {fused_shape} bf16 causal fwd+bwd "
                   f"via dispatch_op: path {fused_path}; vs jax.numpy fwd "
                   f"{err:.2e} grad {gerr:.2e} of scale (tolerance {tol:g})")

    Sl = paged_slots
    tables = rng.permutation(np.arange(1, num_blocks))[
        :Sl * pages_per_seq].reshape(Sl, pages_per_seq).astype('int32')
    lens = rng.randint(1, pages_per_seq * block_size + 1, Sl).astype('int32')
    lens[::2] = pages_per_seq * block_size
    paged_err = {}
    for Hp, Dp, kv_dtype in paged_cases:
        pscale = 1.0 / math.sqrt(Dp)
        shape = (num_blocks, block_size, row_lanes(Hp * Dp))
        qd = rng.randn(Sl, Hp, Dp).astype('float32')
        inputs = {'q': qd, 'block_tables': tables, 'context_lens': lens}
        stored = {}                 # the pool's values, decoded to float64
        for name in ('k', 'v'):
            if kv_dtype == 'int8':
                pages = rng.randint(-127, 128, shape).astype('int8')
                scales = rng.uniform(0.002, 0.02, shape[:2] + (Hp,)) \
                    .astype('float32')
                inputs[name + '_scales'] = scales
                stored[name] = pages[..., :Hp * Dp].reshape(
                    shape[:2] + (Hp, Dp)).astype('float64') \
                    * scales[..., None]
            else:
                pages = np.asarray(jnp.asarray(
                    rng.randn(*shape),
                    jnp.bfloat16 if kv_dtype == 'bf16' else jnp.float32))
                stored[name] = np.asarray(pages, 'float64')[
                    ..., :Hp * Dp].reshape(shape[:2] + (Hp, Dp))
            inputs[name + '_pages'] = pages
        with dygraph.guard():
            got = np.asarray(dispatch_op('paged_attention', inputs,
                                         {'sm_scale': pscale}).numpy())
        want = np.zeros_like(got)
        for s in range(Sl):         # plain numpy, f64: the block walk itself
            ks = stored['k'][tables[s]].reshape(-1, Hp, Dp)[:lens[s]]
            vs = stored['v'][tables[s]].reshape(-1, Hp, Dp)[:lens[s]]
            sc = np.einsum('hd,thd->ht', qd[s].astype('float64'),
                           ks) * pscale
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            want[s] = np.einsum('ht,thd->hd',
                                pr / pr.sum(-1, keepdims=True), vs)
        assert np.isfinite(got).all()
        perr = float(np.abs(got - want).max() / np.abs(want).max())
        assert perr <= paged_tol, (Hp, Dp, kv_dtype, perr, paged_tol)
        paged_err[f'{Hp}x{Dp}_{kv_dtype}'] = perr
    say('kernels', f"ok paged_attention, {Sl} slots of {pages_per_seq} "
                   f"blocks of {block_size} via dispatch_op, vs numpy as a "
                   f"share of scale (tolerance {paged_tol:g}): "
                   + ', '.join(f'{name} {e:.2e}'
                               for name, e in paged_err.items()))
    experts_path, experts_err = experts_check(experts, expert_calls, tol)
    say('kernels', f"ok moe_experts, {experts[0]} experts of {experts[1]} x "
                   f"{experts[2]} bf16, tokens x top_k: path {experts_path}"
                   "; vs a per-expert numpy loop as a share of scale "
                   f"(tolerance {tol:g}): "
                   + ', '.join(f'{name} {e:.2e}'
                               for name, e in experts_err.items()))
    return {'fused_attention': fused_path, 'moe_experts': experts_path,
            'err': {'fused_fwd': err, 'fused_grad': gerr,
                    'paged': paged_err, 'experts': experts_err}}


# the four served callers of the grouped read at their cells' shapes: (name,
# the caller's scope, slots, query heads, key/value heads, query rows a slot,
# head_dim, the pool's lanes, blocks a slot's table or ring, span, one array
# for keys and values, contexts drawn from [lo, hi])
GROUP_READS = (
    ('trinity_full', 'kv/decode_read', 24, 48, 8, 1, 128, 1024, 1056, 0,
     False, 512, 16896),
    ('trinity_sliding', 'kv/sliding_read', 24, 48, 8, 1, 128, 1024, 257,
     4096, False, 512, 16896),
    ('sdar_block', 'kv/block_read', 128, 32, 4, 4, 128, 512, 160, 0, False,
     64, 2560),
    ('kanana2_latent', 'mla/decode_read', 128, 32, 1, 1, 640, 640, 280, 0,
     True, 128, 4480),
    ('lfm2_grouped', 'kv/decode_read', 128, 32, 8, 1, 64, 512, 280, 0,
     False, 128, 4480),
)


def group_read_phase(cases=GROUP_READS, block_size=16, tol=2e-2, calls=10,
                     seed=13):
    """The grouped read (`ops/nn_ops.py::_live_group_attention`) at the
    served cells' shapes, bf16 pools: per case one slot at 1 position, one
    at its whole table, one idle on the scratch block, the others drawn.
    The op is reached as a model's forward reaches it (a jit of its own
    under the caller's scope, inside the program's jit) and compiled for
    this backend. Where its predicate holds (a TPU) the compiled program
    must hold ONE Mosaic custom call (ops/pallas_group_read.py) with the
    scope in its `op_name`, which the benchmark sums device time by, and no
    `while` (the XLA walk's loop). Its result is held to the XLA walk's
    (`_live_group_walk`) at ``tol`` of the output scale (both round the
    probabilities and the result to bf16), and both are timed over
    ``calls`` calls: µs a call and the share of HBM speed at which the
    attended rows cross (819 GB/s; informational). Returns {case: figures}."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops.pallas_group_read import group_read_kernel_applies
    from paddle_tpu.ops.pallas_moe import kernel_op_names

    rng = np.random.RandomState(seed)
    out = {}
    for (name, scope, slots, heads, groups, rows, head_dim, lanes, width,
         span, shared, lo, hi) in cases:
        blocks = 1 + slots * width
        tables = (1 + rng.permutation(slots * width)).reshape(
            slots, width).astype('int32')
        tables[-1] = 0                              # an idle slot
        ctx = rng.randint(lo, hi + 1, slots).astype('int32')
        ctx[0], ctx[1], ctx[-1] = 1, width * block_size, 1
        if not span:
            ctx = np.minimum(ctx, width * block_size)
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        k = jax.random.normal(keys[0], (blocks, block_size, lanes),
                              jnp.bfloat16)
        v = k if shared else jax.random.normal(
            keys[1], (blocks, block_size, lanes), jnp.bfloat16)
        q = jax.random.normal(keys[2], (slots, heads, rows, head_dim),
                              jnp.bfloat16)
        context = jnp.asarray(ctx)
        if span:
            live = jax.jit(nn_ops.live_ring_group_list, static_argnums=(
                2, 3))(jnp.asarray(tables), context, block_size, span)
        else:
            live = jax.jit(nn_ops.live_group_list, static_argnums=(2,))(
                jnp.asarray(tables), context, block_size)
        scale = 1.0 / math.sqrt(head_dim)
        op = jax.jit(lambda q, k, v, c, live: nn_ops._live_group_attention(
            q, k, k if shared else v, c, live, groups, scale, span))

        def scoped(*args):
            with jax.named_scope(scope):
                return op(*args)
        args = (q, k, v, context, tuple(live))
        read = jax.jit(scoped).lower(*args).compile()
        walk = jax.jit(lambda q, k, v, c, live: nn_ops._live_group_walk(
            q, k, k if shared else v, c, live, groups, scale, span)
        ).lower(*args).compile()
        kernel = group_read_kernel_applies(q, k)
        if kernel:
            text = read.as_text()
            names = kernel_op_names(text)
            assert len(names) == 1 and f'/{scope}/' in names[0], names
            assert not [line for line in text.splitlines()
                        if ' while(' in line], name
        figures = {}
        for label, fn in (('read', read), ('walk', walk)):
            got = fn(*args)
            got.block_until_ready()
            t = time.perf_counter()
            for _ in range(calls):
                again = fn(*args)
            again.block_until_ready()
            figures[label] = (np.asarray(got, np.float32),
                              (time.perf_counter() - t) / calls)
        want = figures['walk'][0]
        got = figures['read'][0]
        assert got.shape == q.shape and np.isfinite(got).all(), name
        err = float(np.abs(got - want).max() / np.abs(want).max())
        assert err <= tol, (name, err, tol)
        attended = int(np.minimum(ctx, span).sum() if span else ctx.sum())
        nbytes = attended * lanes * 2 * (1 if shared else 2)
        out[name] = {'path': 'pallas kernel' if kernel else 'XLA walk',
                     'err': err,
                     'us': {k: round(t * 1e6, 1)
                            for k, (_, t) in figures.items()},
                     'hbm_share': {k: round(nbytes / t / 819e9, 3)
                                   for k, (_, t) in figures.items()}}
        say('group_read', f"ok {name} under {scope}: path "
                          f"{out[name]['path']}, vs the XLA walk {err:.2e} "
                          f"of scale (tolerance {tol:g}); "
                          f"{out[name]['us']['read']} µs a call, the walk "
                          f"{out[name]['us']['walk']} µs "
                          f"({nbytes / 1e6:.1f} MB attended)")
    return out


# -- static ------------------------------------------------------------------

def static_phase(counter, batch=128, steps=24, seed=0):
    """The Fluid front door (BASELINE config[0] at its own full size):
    build_static_lenet under fluid.Executor(fluid.TPUPlace(0)), Adam, fed
    from the DataLoader ring with the seeded synthetic MNIST stream (random
    pixels, random labels: what falls is the loss toward ln 10 as the net
    learns the label prior, which is enough to show gradients, the optimizer
    update, state donation and feed staging all work on the device)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, native
    from paddle_tpu import observability as obs
    from paddle_tpu import reader as R
    from paddle_tpu.core.random import seed as set_seed
    from paddle_tpu.datasets import mnist_train
    from paddle_tpu.models.lenet import build_static_lenet

    assert steps >= 10
    set_seed(seed)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data('img', [1, 28, 28])
        label = layers.data('label', [1], dtype='int64')
        loss, _, _ = build_static_lenet(img, label)
        fluid.optimizer.Adam(1e-3).minimize(loss)
        loader = fluid.io.DataLoader.from_generator(
            feed_list=[img, label], capacity=4)
    place = fluid.TPUPlace(0)
    batched = R.batch(mnist_train(), batch, drop_last=True)

    def batches():
        # exactly `steps` batches (the synthetic stream is 1024 samples and
        # restarts), so the loader's staging thread ends with the loop
        passes = itertools.chain.from_iterable(
            batched() for _ in itertools.count())
        return itertools.islice(passes, steps)

    loader.set_sample_list_generator(batches, places=place)
    losses = []
    with obs.telemetry_guard(True), fluid.scope_guard(fluid.Scope()):
        obs.reset()
        exe = fluid.Executor(place)
        exe.run(startup)
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        for feed in loader():
            out, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.ravel(out)[0]))
            if len(losses) == 1:
                first_s = time.perf_counter() - t0
                c1 = counter.snapshot()
        assert len(losses) == steps, len(losses)
        after_first = counter.since(c1)
        metrics = obs.registry.to_dict()
        misses = _metric_total(metrics, 'compile_cache_misses')
        hits = _metric_total(metrics, 'compile_cache_hits')
    assert all(math.isfinite(l) for l in losses), losses
    head, tail = np.mean(losses[:5]), np.mean(losses[-5:])
    assert tail < head, f'loss did not fall: {losses}'
    # the train program was lowered once and every later step hit the cache
    # (the startup program initialises eagerly and is not counted)
    assert (misses, hits) == (1, steps - 1), (misses, hits)
    assert after_first['compiles'] == 0, \
        f'compiles after the first step: {after_first}'
    is_native = native.is_native()
    say('static', f"ok LeNet build_static_lenet Executor(TPUPlace(0)) Adam "
                  f"bs{batch} steps={steps} DataLoader ring, synthetic MNIST:"
                  f" loss {losses[0]:.4f} -> {losses[-1]:.4f} (first-5 mean "
                  f"{head:.4f} > last-5 mean {tail:.4f}); program lowered "
                  f"once (compile_cache_misses {misses} hits {hits}, 0 XLA "
                  f"compiles after step 1); "
                  f"paddle_tpu.native.is_native()={is_native}")
    say('static', f"informational: first step {first_s:.1f} s "
                  f"({counter.since(c0)['compiles']} XLA compiles)")
    return {'losses': [round(l, 4) for l in losses], 'is_native': is_native,
            'first_step_s': round(first_s, 2)}


# -- four chips --------------------------------------------------------------

def _bert_batch(cfg, n, seq, masked_per_seq, seed):
    """A fixed pretraining batch with the SAME number of masked positions in
    every sequence, so the MLM mean over any equal split of the batch equals
    the mean over the whole batch (what lets one chip reproduce the global
    batch by gradient accumulation)."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (n, seq)).astype(np.int64)
    tt = np.zeros((n, seq), np.int64)
    mlm = np.full((n, seq), -1, np.int64)
    for row in range(n):
        pos = rng.choice(seq, masked_per_seq, replace=False)
        mlm[row, pos] = rng.randint(0, cfg.vocab_size, masked_per_seq)
    nsp = rng.randint(0, 2, (n, 1)).astype(np.int64)
    return ids, tt, mlm, nsp


def train_dp4_phase(counter, cfg=None, seq=128, per_chip=128, steps=5,
                    n_dev=4, loss_tol=1e-2, seed=0):
    """BASELINE config[2] through its normal entry point:
    fleet.init(mesh_shape={'dp': n_dev}) + TrainStep(data_sharding=
    data_sharding()), BERT-base, bf16, Adam, `per_chip` sequences per chip,
    a fixed batch.

    The one-chip trajectory of the SAME global batch comes from
    TrainStep(accum_steps=n_dev) on device 0 over the n_dev shards in order
    (the whole batch does not fit one chip's memory): the optimizer applies
    once on the mean of the shard gradients, which is the global-batch
    gradient because every shard holds the same number of masked positions.
    Dropout is off for both: its keys are trace-time constants whose masks
    depend on the batch shape, so two partitions of one batch would differ.

    loss_tol is relative: the two runs do the same arithmetic per sequence
    in bf16 (epsilon 2^-8 = 3.9e-3) and differ in the order f32 gradients
    are summed (all-reduce across chips vs accumulation in place), which
    Adam's normalisation carries into the next step's loss."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import dygraph
    from paddle_tpu.core.random import seed as set_seed
    from paddle_tpu.dygraph.jit import TrainStep
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretrain_loss)
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.mesh import data_sharding
    from paddle_tpu.partition import reset_partitioner

    assert len(jax.devices()) >= n_dev
    cfg = copy.copy(cfg) if cfg is not None else BertConfig.base()
    cfg.hidden_dropout_prob = cfg.attention_probs_dropout_prob = 0.0
    batch = _bert_batch(cfg, n_dev * per_chip, seq, max(seq * 15 // 100, 1),
                        seed)

    def build():
        set_seed(seed)
        model = BertForPretraining(cfg)
        opt = fluid.optimizer.Adam(1e-4, parameter_list=model.parameters())
        return model, opt

    # one chip, the same global batch, by accumulation over its shards
    with dygraph.guard():
        model, opt = build()
        step = TrainStep(model, pretrain_loss, opt, amp_dtype=jnp.bfloat16,
                         accum_steps=n_dev)
        ref = []
        for _ in range(steps):
            micro = [step(*(a[i * per_chip:(i + 1) * per_chip]
                            for a in batch)) for i in range(n_dev)]
            ref.append(float(np.mean([float(l) for l in micro])))
        del model, opt, step, micro
    gc.collect()

    fleet.init(mesh_shape={'dp': n_dev})
    try:
        with dygraph.guard():
            model, opt = build()
            sharding = data_sharding()
            mesh_devs = set(sharding.mesh.devices.flat)
            assert len(mesh_devs) == n_dev
            step = TrainStep(model, pretrain_loss, opt,
                             data_sharding=sharding, amp_dtype=jnp.bfloat16)
            t0 = time.perf_counter()
            losses = [jax.block_until_ready(step(*batch))]
            first_s = time.perf_counter() - t0
            state = [p.value for p in model.parameters()] + \
                [v for slots in step._slots.values() for v in slots.values()]
            resident = sum(set(a.devices()) == mesh_devs
                           and a.is_fully_addressable for a in state)
            assert resident == len(state), \
                f'{resident} of {len(state)} params+slots on all devices'
            c1 = counter.snapshot()
            losses.append(jax.block_until_ready(step(*batch)))
            step2 = counter.since(c1)
            assert step2['compiles'] == 0, f'step 2 compiled: {step2}'
            t0 = time.perf_counter()
            for _ in range(steps - 2):
                losses.append(step(*batch))
            jax.block_until_ready(losses[-1])
            warm_ms = (time.perf_counter() - t0) / max(steps - 2, 1) * 1e3
            losses = [float(l) for l in losses]
            assert step._jitted._cache_size() == 1
            mem = [(d.memory_stats() or {}).get('bytes_in_use')
                   for d in sorted(mesh_devs, key=lambda d: d.id)]
    finally:
        reset_partitioner()

    assert all(math.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f'loss did not fall: {losses}'
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
    assert max(rel) <= loss_tol, (losses, ref, rel)
    if all(m is not None for m in mem):       # the CPU client reports none
        assert max(mem) <= 4 * min(mem), f'bytes_in_use uneven: {mem}'
    say('train_dp4', f"ok BERT-base h={cfg.hidden_size} "
                     f"L={cfg.num_hidden_layers} S={seq} {per_chip} seq/chip "
                     f"x dp={n_dev} bf16 Adam steps={steps}: after step 1 "
                     f"{resident}/{len(state)} params+slots resident on all "
                     f"{n_dev} devices; step 2 compiles {step2['compiles']} "
                     f"(jit cache 1); bytes_in_use per device {mem}")
    say('train_dp4', f"loss dp{n_dev} {[round(l, 4) for l in losses]} vs one "
                     f"chip, same global batch (accum_steps={n_dev}) "
                     f"{[round(l, 4) for l in ref]}: max rel diff "
                     f"{max(rel):.2e} (tolerance {loss_tol:g})")
    say('train_dp4', f"informational: first step {first_s:.1f} s, warm wall "
                     f"{warm_ms:.1f} ms/step")
    return {'losses': [round(l, 4) for l in losses],
            'one_chip_losses': [round(l, 4) for l in ref],
            'max_rel_diff': max(rel), 'bytes_in_use': mem,
            'first_step_s': round(first_s, 2),
            'warm_ms_per_step': round(warm_ms, 2)}


# -- main --------------------------------------------------------------------

def result_line(device):
    """The last line of stdout: exactly the keys the driver's check reads."""
    return json.dumps({'ok': True,
                       'device': {'platform': device['platform'],
                                  'kind': device['kind'],
                                  'count': device['count']}})


def main():
    import jax

    import paddle_tpu  # noqa: F401  (a bare directory fails here, unprinted)
    backend = jax.default_backend()
    if backend != 'tpu':
        sys.exit(f'chip_smoke: needs a TPU backend, found {backend!r} '
                 f'({len(jax.devices())} device(s): {jax.devices()[0]}). '
                 'This script is the on-chip proof and does not run '
                 'elsewhere; tests/framework/test_chip_smoke.py runs its '
                 'phases on the CPU at tiny sizes.')
    t0 = time.perf_counter()
    counter = CompileCounter()
    device = device_phase()
    phases = {
        'train': train_phase(counter),
        'serve': serve_phase(counter),
        'kernels': kernels_phase(),
        'group_read': group_read_phase(),
        'static': static_phase(counter),
    }
    gc.collect()
    if device['count'] >= 4:
        phases['train_dp4'] = train_dp4_phase(counter)
    else:
        say('train_dp4', f"not run, {device['count']} device(s)")
        phases['train_dp4'] = None
    total = counter.snapshot()
    say('cache', f"persistent compile cache at "
                 f"{jax.config.jax_compilation_cache_dir}: requests "
                 f"{total['requests']} hits {total['hits']} writes "
                 f"{total['writes']}; {total['compiles']} executables built "
                 f"or loaded, {total['compile_secs']:.1f} s in XLA")
    say('done', f'all phases green in {time.perf_counter() - t0:.0f} s')
    say('summary', json.dumps({'phases': phases, 'compile_cache': total,
                               'seconds': round(time.perf_counter() - t0, 1),
                               'claim': None}))
    print(result_line(device), flush=True)


if __name__ == '__main__':
    main()
