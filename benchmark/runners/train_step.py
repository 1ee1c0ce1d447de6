"""Runner `train_step`: a dygraph model under dygraph.jit.TrainStep, on one
chip or over a fleet mesh, fed device-resident batches in rotation.

Set-up (all counted in setup_s): weights on the device from the seed in one
jitted call; the seeded batches in another; the plain reference's two losses
(cached per checkout, by configuration, traffic, seed and reference file);
the step program's first two losses against them (batch 0, and batch 0 again
after one update, which a skipped or unsynchronised update would leave where
it was); a few warm steps over the other batches.

Window: steps are enqueued back to back, the host never more than
`inflight_steps` ahead of the device (it waits for the loss of that many
steps ago, which a training loop that logs its loss does too), until
--seconds have passed; the window closes when the last enqueued step has
finished, so it holds whole steps only. The rate is steps x global batch
over that time. A step whose loss is not finite has failed.

Traced run: the same window for the rate, then `trace_steps` steps under
jax.profiler for the device metrics.
"""
from __future__ import annotations

import hashlib
import json
import math
import os
import time

# idle gaps go to the innermost of these that covers them
SPAN_NAMES = ['train_step/build', 'train_step/execute', 'train_step/call',
              'harness/dispatch']


def _reference_losses(ctx, reference, program, params, batches, devices):
    """The reference's numbers depend only on the files that make its
    inputs and compute it, the configuration, the traffic and the seed:
    computed once per checkout."""
    import jax
    digest = hashlib.sha256()
    for path in (reference.__file__, program.__file__,
                 ctx.find('lib', 'build', '.py')):
        with open(path, 'rb') as f:
            digest.update(f.read())
    digest.update(json.dumps([ctx.config, ctx.traffic, ctx.seed,
                              devices[0].device_kind, jax.__version__],
                             sort_keys=True).encode())
    cache = os.path.join(os.path.dirname(ctx.out_dir), '.reference',
                         digest.hexdigest()[:24] + '.json')
    if os.path.exists(cache):
        with open(cache) as f:
            return json.load(f), True
    one = devices[0]
    p = {n: jax.device_put(v, one) for n, v in params.items()}
    batch = tuple(jax.device_put(a, one) for a in batches[0])
    with jax.default_matmul_precision('highest'):
        out = reference.losses(ctx.config, p, batch)
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    with open(cache, 'w') as f:
        json.dump(out, f)
    return out, False


def run(ctx):
    import jax

    from paddle_tpu import dygraph

    mesh = ctx.traffic.get('mesh')
    n_dev = math.prod(mesh.values()) if mesh else 1
    sharding = None
    if mesh:
        from paddle_tpu.parallel import fleet
        from paddle_tpu.parallel.mesh import data_sharding
        fleet.init(mesh_shape=mesh)
        sharding = data_sharding()
    try:
        with dygraph.guard():
            return _run(ctx, jax.devices()[:n_dev], sharding)
    finally:
        if mesh:        # the partitioner is process-wide
            from paddle_tpu.partition import reset_partitioner
            reset_partitioner()


def _run(ctx, devices, sharding):
    import jax
    import jax.numpy as jnp

    from paddle_tpu import observability as obs
    from paddle_tpu.dygraph.jit import TrainStep

    config, traffic = ctx.config, ctx.traffic
    family = config['family']
    program = ctx.module('programs', family)
    reference = ctx.module('reference', family)
    flops = ctx.module('flops', family)
    build = ctx.module('lib', 'build')
    n_dev = len(devices)
    global_batch = traffic['batch_per_chip'] * n_dev
    compute = config['dtype_policy']['compute']
    c0 = ctx.counter.snapshot()

    model = build.model_on_device(lambda: program.build(config), ctx.seed)
    optimizer = program.optimizer(config, model)
    ctx.phase('weights')
    keys = jax.random.split(jax.random.PRNGKey(ctx.seed + 1),
                            traffic['resident_batches'])
    batches = jax.jit(
        lambda ks: [program.batch(k, config, traffic, global_batch)
                    for k in ks], out_shardings=sharding)(keys)
    jax.block_until_ready(batches)
    ctx.phase('batches')
    params = {n: p.value for n, p in model.named_parameters()}
    ref, cached = _reference_losses(ctx, reference, program, params, batches,
                                    devices)
    ctx.info(f'reference {ref} ({"cached" if cached else "computed"})')
    del params
    ctx.phase('reference')

    step = TrainStep(model, program.loss_fn, optimizer,
                     data_sharding=sharding,
                     amp_dtype=None if compute == 'float32'
                     else jnp.dtype(compute))
    loss0 = float(step(*batches[0]))
    ctx.phase('first step')
    loss1 = float(step(*batches[0]))    # the same batch, after one update
    warm = [step(*batches[i % len(batches)])
            for i in range(1, 1 + traffic['warm_steps'])]
    jax.block_until_ready(warm)
    ctx.phase('warm steps')
    setup_compiles = ctx.counter.since(c0)

    # -- the window ----------------------------------------------------
    inflight = traffic['inflight_steps']
    obs.reset()
    c1 = ctx.counter.snapshot()
    losses, dispatch = [], []
    error = None
    window_open = time.perf_counter()
    deadline = window_open + ctx.seconds
    try:
        while True:
            batch = batches[len(losses) % len(batches)]
            a = time.perf_counter_ns()
            loss = step(*batch)
            dispatch.append((a, time.perf_counter_ns()))
            losses.append(loss)
            if len(losses) >= inflight:
                jax.block_until_ready(losses[-inflight])
            if len(losses) % 32 == 8:
                ctx.sample_memory()     # with steps in flight
            if time.perf_counter() >= deadline:
                break
        jax.block_until_ready(losses[-1])
    except Exception as e:      # a step that raises has failed
        error = f'{type(e).__name__}: {e}'
    window_s = time.perf_counter() - window_open
    window_compiles = ctx.counter.since(c1)
    values = [float(v) for v in jax.device_get(losses)]
    failed = sum(not math.isfinite(v) for v in values) + (error is not None)

    # -- the traced slice ---------------------------------------------
    spans = []
    if ctx.traced:
        with ctx.profile():
            traced = []
            for i in range(traffic['trace_steps']):
                a = time.perf_counter_ns()
                traced.append(step(*batches[i % len(batches)]))
                spans.append(('harness/dispatch', a, time.perf_counter_ns()))
            jax.block_until_ready(traced)
        spans += ctx.module('lib', 'spans').program_spans(obs, SPAN_NAMES)

    tol = config['check']['loss_tolerance']
    err0 = abs(loss0 - ref['loss0']) / abs(ref['loss0'])
    err1 = abs(loss1 - ref['loss1']) / abs(ref['loss1'])
    update_effect = abs(ref['loss1'] - ref['loss0']) / abs(ref['loss1'])
    checks = {
        'loss0': loss0, 'loss1': loss1, 'reference': ref,
        'loss0_rel_err': err0, 'loss1_rel_err': err1, 'tolerance': tol,
        'update_moves_loss1_by': update_effect,
        'losses_within_tolerance': err0 <= tol and err1 <= tol,
        'window_losses_finite': failed == 0,
        'no_compile_in_window': window_compiles['compiles'] == 0,
        'error': error,
    }
    correct = (checks['losses_within_tolerance']
               and checks['window_losses_finite']
               and checks['no_compile_in_window'])
    steps = len(values)
    dispatch_s = [(b - a) * 1e-9 for a, b in dispatch]
    ctx.info(f'{steps} steps of {global_batch} in {window_s:.3f} s; loss '
             f'{values[:1]} -> {values[-1:]}; dispatch ms '
             f'{ctx.stats.summary(dispatch_s, 1e3)}')
    return {
        'runner': 'train_step', 'window_open': window_open,
        'window_s': window_s, 'attempted': steps, 'failed': failed,
        'correct': correct, 'checks': checks,
        'counts': {'steps': steps, 'samples': steps * global_batch,
                   'global_batch': global_batch, 'chips': n_dev},
        'samples': {'dispatch_s': dispatch_s},
        'flops_per_sample': flops.per_sample(config, traffic),
        'compiles': {'setup': setup_compiles, 'window': window_compiles},
        'spans': spans,
        'span_names': SPAN_NAMES,
    }
