"""IR pass-pipeline microbench (PERF.md §10).

For three static-graph training programs — a multi-param Adam MLP, a
ResNet bottleneck block (conv+BN+momentum), and a BERT-style transformer
layer (attention+layer_norm+adam) — measures, pass pipeline OFF vs ON
(with the BuildStrategy fuse knobs live):

- global-block op count the tracer walks,
- total jaxpr equation count of the lowered step (nested jaxprs included),
- trace+lower wall seconds (pipeline run + `_lower` + jax.jit().lower(),
  i.e. everything before XLA's backend compile),
- `executor_compile_seconds` through the real Executor path under
  telemetry, for the end-to-end number PR 2's metric records.

One JSON line per model. Runs on any backend; sized for CPU:

  JAX_PLATFORMS=cpu python tools/bench_passes.py [--iters 3] [--smoke]

The multi-param Adam model is the acceptance bench: with
`fuse_all_optimizer_ops=True` the eqn count must drop ≥30% (asserted in
tier-1 by tests/framework/test_bench_passes.py at smoke sizes).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# runnable as `python tools/bench_passes.py` from the repo root
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# model builders (shared with tests/framework/test_ir_passes.py)
# ---------------------------------------------------------------------------

def build_mlp_adam(smoke=False, layers_n=None):
    """Deep MLP under Adam: #params scales with depth, so the per-param
    update-op tail dominates the traced program — the fuse_all_optimizer_ops
    showcase. Returns (main, startup, make_feed, fetch_var)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers as L
    # "multi-param" must mean it even at smoke sizes: below ~12 layers the
    # update ops are too small a fraction of the program for the bundle
    # rewrite to clear its own reshape/slice overhead
    width = 16 if smoke else 64
    depth = layers_n if layers_n is not None else (16 if smoke else 24)
    bs = 4 if smoke else 32
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [width], dtype='float32')
        y = L.data('y', [1], dtype='float32')
        h = x
        for _ in range(depth):
            h = L.fc(h, size=width, act='relu')
        pred = L.fc(h, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)

    def make_feed():
        return {'x': rng.randn(bs, width).astype(np.float32),
                'y': rng.randn(bs, 1).astype(np.float32)}

    return main, startup, make_feed, loss


def build_resnet_block(smoke=False):
    """Static ResNet bottleneck (1×1 → 3×3 → 1×1 convs, BN, relu,
    shortcut) under Momentum — conv/BN trace cost + fused momentum tail."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers as L
    ch, hw, bs = (8, 6, 2) if smoke else (32, 12, 4)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [ch, hw, hw], dtype='float32')
        y = L.data('y', [1], dtype='float32')

        def conv_bn(inp, ch_out, k, act=None):
            c = L.conv2d(inp, ch_out, k, padding=(k - 1) // 2,
                         bias_attr=False)
            return L.batch_norm(c, act=act)

        h = conv_bn(x, ch // 2, 1, act='relu')
        h = conv_bn(h, ch // 2, 3, act='relu')
        h = conv_bn(h, ch, 1)
        h = L.relu(L.elementwise_add(h, x))
        pool = L.reduce_mean(h, dim=[2, 3])
        pred = L.fc(pool, size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.Momentum(learning_rate=1e-2,
                                 momentum=0.9).minimize(loss)
    rng = np.random.RandomState(0)

    def make_feed():
        return {'x': rng.randn(bs, ch, hw, hw).astype(np.float32),
                'y': rng.randn(bs, 1).astype(np.float32)}

    return main, startup, make_feed, loss


def build_bert_layer(smoke=False):
    """Static transformer layer: QKV projections, scaled-dot attention,
    residual + layer_norm, GELU FFN — fc-heavy, so add+act fusion and the
    Adam tail both engage."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers as L
    hid, seq, heads, bs = (16, 4, 2, 1) if smoke else (64, 16, 4, 2)
    dh = hid // heads
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = L.data('x', [seq, hid], dtype='float32')
        y = L.data('y', [1], dtype='float32')

        def proj(inp, act=None):
            return L.fc(inp, size=hid, num_flatten_dims=2, act=act)

        q, k, v = proj(x), proj(x), proj(x)

        def split_heads(t):
            t = L.reshape(t, shape=[0, seq, heads, dh])
            return L.transpose(t, perm=[0, 2, 1, 3])

        qh, kh, vh = split_heads(q), split_heads(k), split_heads(v)
        scores = L.scale(L.matmul(qh, kh, transpose_y=True),
                         scale=1.0 / np.sqrt(dh))
        ctxv = L.matmul(L.softmax(scores), vh)
        ctxv = L.reshape(L.transpose(ctxv, perm=[0, 2, 1, 3]),
                         shape=[0, seq, hid])
        attn_out = proj(ctxv)
        h = L.layer_norm(L.elementwise_add(attn_out, x), begin_norm_axis=2)
        ffn = L.fc(h, size=hid * 2, num_flatten_dims=2, act='gelu')
        ffn = L.fc(ffn, size=hid, num_flatten_dims=2)
        h2 = L.layer_norm(L.elementwise_add(ffn, h), begin_norm_axis=2)
        pred = L.fc(L.reduce_mean(h2, dim=[1]), size=1)
        loss = L.reduce_mean(L.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)

    def make_feed():
        return {'x': rng.randn(bs, seq, hid).astype(np.float32),
                'y': rng.randn(bs, 1).astype(np.float32)}

    return main, startup, make_feed, loss


MODELS = {'mlp_adam': build_mlp_adam, 'resnet_block': build_resnet_block,
          'bert_layer': build_bert_layer}


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _fused_build_strategy():
    from paddle_tpu.compiler import BuildStrategy
    bs = BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    bs.fuse_all_optimizer_ops = True
    return bs


def count_eqns(jaxpr):
    """Total equations including nested (pjit/cond/scan/remat) jaxprs."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                total += count_eqns(sub)
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


def _lowered_step(program, feed_vals, fetch_name, state, passes_on):
    """(step fn, optimized program) after optionally running the pipeline —
    the pass cost itself is part of the measured trace+lower time."""
    from paddle_tpu import ir
    from paddle_tpu.executor import _lower
    if passes_on:
        program, _ = ir.apply_pipeline(
            program, fetch_names=[fetch_name], feed_names=list(feed_vals),
            build_strategy=_fused_build_strategy())
    step = _lower(program, sorted(feed_vals), [fetch_name],
                  sorted(state))
    return step, program


def measure_model(name, builder, iters=3, smoke=False):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu import ir

    main, startup, make_feed, loss = builder(smoke)
    exe = fluid.Executor()
    exe.run(startup)
    scope = fluid.global_scope()
    state = {v.name: jnp.asarray(scope.find(v.name))
             for v in main.list_vars() if v.persistable}
    feed_vals = {k: jnp.asarray(v) for k, v in make_feed().items()}
    key = jax.random.PRNGKey(0)

    out = {'bench': f'passes_{name}'}
    for tag, on in (('off', False), ('on', True)):
        step, prog = _lowered_step(main, feed_vals, loss.name, state, on)
        jaxpr = jax.make_jaxpr(step)({}, state, feed_vals, key)
        t0 = time.perf_counter()
        for _ in range(iters):
            step_i, _ = _lowered_step(main, feed_vals, loss.name, state, on)
            jax.jit(step_i, donate_argnums=(0,)).lower(
                {}, state, feed_vals, key)
        dt = (time.perf_counter() - t0) / iters
        out[f'ops_{tag}'] = len(prog.global_block().ops)
        out[f'eqns_{tag}'] = count_eqns(jaxpr.jaxpr)
        out[f'trace_lower_ms_{tag}'] = round(dt * 1e3, 3)
    out['eqn_reduction'] = round(1 - out['eqns_on'] / out['eqns_off'], 4)
    out['op_reduction'] = round(1 - out['ops_on'] / out['ops_off'], 4)
    out['trace_lower_speedup'] = round(
        out['trace_lower_ms_off'] / max(out['trace_lower_ms_on'], 1e-9), 3)
    return out


def measure_executor_compile(iters=2, smoke=True):
    """executor_compile_seconds (PR 2 telemetry) for the mlp_adam program,
    pipeline off vs on through the REAL Executor.run path, in both compile
    regimes:

    - cold: persistent XLA cache disabled — trace + lower + full backend
      compile (the one-time-EVER cost per program, amortized across
      processes by PR 1's persistent cache);
    - warm: persistent cache pre-populated — trace + lower + executable
      deserialize, i.e. what EVERY cold process start pays in production.
      The pass pipeline targets exactly this number: the trace is the one
      cost the compile cache cannot amortize.

    Identical feed shapes per off/on pair; a fresh Executor (fresh jit
    closure) per run forces a real retrace."""
    import tempfile
    import numpy as np
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.compiler import CompiledProgram

    main, startup, make_feed, loss = build_mlp_adam(smoke)
    fluid.Executor().run(startup)
    base_feed = make_feed()

    def run_once(passes_on, batch, cache_dir):
        feed = {k: np.repeat(v, batch, axis=0) for k, v in base_feed.items()}
        old_env = os.environ.get('PADDLE_TPU_PASSES')
        os.environ['PADDLE_TPU_PASSES'] = '1' if passes_on else '0'
        # drive jax's cache config directly: Executor.setup_persistent_cache
        # configures it at most once per process, which would leave earlier
        # experiments' settings live and taint the A/B
        old_dir = jax.config.jax_compilation_cache_dir
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        old_sz = jax.config.jax_persistent_cache_min_entry_size_bytes
        old_en = jax.config.jax_enable_compilation_cache
        # jax materializes its cache object once and then ignores config
        # changes; drop it so THIS run's dir/enable settings take effect
        # (private API — best-effort, the enable flag still guards cold)
        try:
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        except Exception:
            pass
        jax.config.update('jax_enable_compilation_cache',
                          cache_dir is not None)
        jax.config.update('jax_compilation_cache_dir', cache_dir)
        if cache_dir is not None:
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', 0.0)
            jax.config.update(
                'jax_persistent_cache_min_entry_size_bytes', -1)
        try:
            with obs.telemetry_guard(True):
                obs.reset()
                exe = fluid.Executor()
                cp = CompiledProgram(main,
                                     build_strategy=_fused_build_strategy())
                exe.run(cp, feed=feed, fetch_list=[loss])
                hist = obs.registry.to_dict()['executor_compile_seconds']
                return sum(s['sum'] for s in hist['samples'])
        finally:
            jax.config.update('jax_enable_compilation_cache', old_en)
            jax.config.update('jax_compilation_cache_dir', old_dir)
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs', old_min)
            jax.config.update(
                'jax_persistent_cache_min_entry_size_bytes', old_sz)
            if old_env is None:
                os.environ.pop('PADDLE_TPU_PASSES', None)
            else:
                os.environ['PADDLE_TPU_PASSES'] = old_env

    cold_off = [run_once(False, 1 + i, None) for i in range(iters)]
    cold_on = [run_once(True, 1 + i, None) for i in range(iters)]
    warm_dir = tempfile.mkdtemp(prefix='bench_passes_xla_cache_')
    warm_off, warm_on = [], []
    for i in range(iters):
        batch = 1 + iters + i
        run_once(False, batch, warm_dir)            # populate
        warm_off.append(run_once(False, batch, warm_dir))
        run_once(True, batch, warm_dir)
        warm_on.append(run_once(True, batch, warm_dir))
    return {'bench': 'passes_executor_compile',
            'cold_compile_s_off': round(min(cold_off), 4),
            'cold_compile_s_on': round(min(cold_on), 4),
            'cold_compile_speedup': round(
                min(cold_off) / max(min(cold_on), 1e-9), 3),
            'warm_compile_s_off': round(min(warm_off), 4),
            'warm_compile_s_on': round(min(warm_on), 4),
            'warm_compile_speedup': round(
                min(warm_off) / max(min(warm_on), 1e-9), 3)}


def _hermetic_compile_cache():
    """Place the persistent XLA cache in a fresh temp dir the way any
    outside caller does — JAX_COMPILATION_CACHE_DIR, which jax reads when
    it is first imported, so this must run before anything imports jax:
    entries an earlier run left in the checkout's cache must not serve
    this bench's compiles."""
    import tempfile
    assert 'jax' not in sys.modules, 'set the cache dir before jax loads'
    os.environ.setdefault(
        'JAX_COMPILATION_CACHE_DIR',
        tempfile.mkdtemp(prefix='bench_passes_xla_cache_'))


def measure_all(iters=3, smoke=False):
    out = {}
    for name, builder in MODELS.items():
        out[name] = measure_model(name, builder, iters=iters, smoke=smoke)
    out['executor_compile'] = measure_executor_compile(
        iters=max(2, iters // 2), smoke=smoke)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--iters', type=int, default=3,
                    help='trace+lower timing repetitions')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny shapes / CI smoke sizes')
    args = ap.parse_args()
    _hermetic_compile_cache()
    for res in measure_all(iters=args.iters, smoke=args.smoke).values():
        print(json.dumps(res), flush=True)


if __name__ == '__main__':
    main()
