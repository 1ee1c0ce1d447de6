"""Runner `serve_decode`: a causal LM behind build_replica_stack +
ServingServer in this process (the one that holds the chip), and the load
generator of lib/loadgen.py in a child process that never imports jax and
speaks HTTP to it, so that the client threads do not take the interpreter
lock from the server's dispatch loop.

Set-up (all counted in setup_s): weights on the device from the seed in one
jitted call; engine.warmup() over the ladder rungs the traffic reaches and
the decode step; prefill-then-decode logits through the paged cache against
the plain reference's whole-sequence forward on a few seeded prompts; the
ramp, which fills the slots (lib/loadgen.py).

Window: what the clients saw between its opening and --seconds later, on the
clients' own clock. Requests are requests sent in the window; a request
fails on any HTTP or stream error, or if its answer does not hold exactly
the tokens asked for.

Traced run: every request carries the program's trace header, so that the
scheduler records replica/queue_wait, replica/prefill and replica/token; a
slice of `trace_slice_s` seconds, a quarter into the window, runs under
jax.profiler.
"""
from __future__ import annotations

import os
import time

import numpy as np

SPAN_NAMES = ['replica/prefill', 'replica/token']


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the prefill's last row, error of one decode
    step] as max |paged - reference| over max |reference|, on seeded prompts:
    the shortest and the longest the traffic allows, and draws from its
    distribution between."""
    import jax
    loadgen = ctx.module('lib', 'loadgen')
    load = ctx.traffic['load']
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    n = ctx.traffic['check_prompts']
    lens = [load['prompt_len']['min']] \
        + [loadgen.quantile_len(load['prompt_len'], rng.random())
           for _ in range(n - 2)] \
        + [load['prompt_len']['max']]
    rows = reference.make_rows(ctx.config)
    errors = []
    for plen in lens:
        prompt = rng.integers(1, load['vocab'], plen).tolist()
        got = []

        def grab(row):
            got.append(np.array(row))
            return int(row.argmax())

        table = engine.reserve_table(plen, 2)
        token = engine.prefill(prompt, table, sampler=grab)
        tokens = [token] + [None] * (engine.slots - 1)
        tables = [table] + [None] * (engine.slots - 1)
        _, step_rows = engine.decode_step(tokens, tables, return_rows=True)
        engine.release_table(table)
        # "highest" for the reference alone: the engine's calls above must
        # run as they are served (f32 at default precision is one bf16 pass)
        with jax.default_matmul_precision('highest'):
            want = np.asarray(rows(params, prompt + [token],
                                   [plen - 1, plen]))
        scale = float(np.abs(want).max())
        errors.append([plen,
                       float(np.abs(got[0] - want[0]).max()) / scale,
                       float(np.abs(step_rows[0] - want[1]).max()) / scale])
    return errors


def run(ctx):
    from paddle_tpu import dygraph, profiler
    from paddle_tpu import observability as obs
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.tier.replica import build_replica_stack

    config, traffic = ctx.config, ctx.traffic
    family = config['family']
    program = ctx.module('programs', family)
    reference = ctx.module('reference', family)
    loadgen = ctx.module('lib', 'loadgen')
    build = ctx.module('lib', 'build')
    c0 = ctx.counter.snapshot()

    with dygraph.guard():
        model = build.model_on_device(lambda: program.build(config),
                                      ctx.seed)
        engine, scheduler, _ = build_replica_stack(model=model,
                                                   **traffic['engine'])
        server = ServingServer(None, host='127.0.0.1', port=0,
                               generator=scheduler,
                               request_timeout=traffic['request_timeout_s'])
        load = None
        ctx.phase('weights, engine')
        try:
            timings = engine.warmup()
            ctx.info(f'warm-up over ladder {engine.prompt_buckets}: '
                     f'{ {k: round(v, 2) for k, v in timings.items()} }')
            ctx.phase('warm-up')
            params = {n: p.value for n, p in model.named_parameters()}
            errors = _logit_check(ctx, engine, params, reference)
            del params
            ctx.phase('logit check')
            profiler.reset_eager_kernel_cache_stats()
            server.start()
            setup_compiles = ctx.counter.since(c0)

            spec = {'port': server.port, 'seed': ctx.seed,
                    'seconds': ctx.seconds, 'traced': ctx.traced,
                    'load': traffic['load'],
                    'request_timeout': traffic['request_timeout_s'],
                    'results': os.path.join(ctx.out_dir, 'load_results.json')}
            load = loadgen.Load(spec, os.path.join(ctx.out_dir,
                                                   'load_spec.json'))
            window_open = load.wait_open()
            ctx.phase('ramp')
            # -- the window ------------------------------------------------
            obs.reset()
            c1 = ctx.counter.snapshot()
            pool_used = []

            def watch(until):
                """Samples, with requests in flight, every half second."""
                while time.perf_counter() < until:
                    ctx.sample_memory()
                    pool_used.append(engine.pool.allocator.used)
                    time.sleep(max(0.0, min(0.5,
                                            until - time.perf_counter())))

            if ctx.traced:
                slice_s = min(traffic['trace_slice_s'], ctx.seconds / 2)
                watch(window_open + ctx.seconds / 4)
                with ctx.profile():
                    time.sleep(slice_s)
            watch(window_open + ctx.seconds)
            results = load.finish(timeout=120)
            window_compiles = ctx.counter.since(c1)
            kernel_cache = profiler.eager_kernel_cache_stats()
            spans = ctx.module('lib', 'spans').program_spans(
                obs, SPAN_NAMES + ['replica/queue_wait'])
            registry = obs.registry.to_dict()
        finally:
            if load is not None:
                load.stop()
            server.shutdown(drain=False)

    seen = loadgen.reduce(results)
    tol = config['check']['logit_tolerance']
    worst = max(max(e[1:]) for e in errors)
    checks = {
        'logit_err_prompt_len_prefill_decode': errors, 'logit_tolerance': tol,
        'logits_within_tolerance': worst <= tol,
        'every_answer_exact': seen['failed'] == 0,
        'errors': seen['errors'],
        'no_compile_in_window': window_compiles['compiles'] == 0,
        'kernel_cache_misses_after_warmup': kernel_cache['misses'],
    }
    correct = (checks['logits_within_tolerance']
               and checks['every_answer_exact']
               and checks['no_compile_in_window']
               and seen['attempted'] > 0)
    ms = {k: ctx.stats.summary(seen[k], 1e3)
          for k in ('ttft_s', 'itl_s', 'send_lag_s')}
    ctx.info(f"window {seen['window_s']:.3f} s: {seen['attempted']} requests "
             f"sent, {seen['completed']} completed, {seen['failed']} failed, "
             f"{seen['censored']} without a first token at the close; "
             f"{seen['tokens']} tokens received")
    ctx.info(f"ttft ms {ms['ttft_s']}; token gap ms {ms['itl_s']}; clients' "
             f"send lag ms {ms['send_lag_s']}")
    ctx.info(f'KV pool blocks held by live requests, of '
             f'{engine.pool.allocator.capacity}: '
             f'{ctx.stats.summary(pool_used, 1)}')
    return {
        'runner': 'serve_decode', 'window_open': window_open,
        'window_s': seen['window_s'], 'attempted': seen['attempted'],
        'failed': seen['failed'], 'correct': correct, 'checks': checks,
        'counts': {'tokens': seen['tokens'], 'completed': seen['completed'],
                   'censored': seen['censored'], 'chips': 1,
                   'pool_blocks': engine.pool.allocator.capacity},
        'samples': dict({k: seen[k]
                         for k in ('ttft_s', 'itl_s', 'send_lag_s')},
                        pool_blocks_used=pool_used),
        'compiles': {'setup': setup_compiles, 'window': window_compiles},
        'registry': registry, 'spans': spans, 'span_names': SPAN_NAMES,
    }
