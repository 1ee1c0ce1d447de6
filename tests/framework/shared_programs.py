"""Programs and workloads shared by more than one test file here.

Static-graph training programs (`build_mlp_adam`, `build_resnet_block`,
`build_bert_layer`: the IR-pass, memory-plan and verifier suites all sweep
them, and `tools/lint_program.py --recipe` lints them); each returns
``(main, startup, make_feed, fetch_var)``. `build_shared_prompt_work` is
the serving tier's motivating request mix; `run_fleet_script` starts a
worker script as a real ``jax.distributed`` fleet. Not a test module: pytest
puts this directory on ``sys.path``, so ``from shared_programs import ...``.
"""
import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers as L


def build_mlp_adam(layers_n=16):
    """Deep MLP under Adam: #params scales with depth, so the per-param
    update-op tail dominates the traced program — the fuse_all_optimizer_ops
    showcase. Returns (main, startup, make_feed, fetch_var)."""
    # "multi-param" must mean it at these sizes too: below ~12 layers the
    # update ops are too small a fraction of the program for the bundle
    # rewrite to clear its own reshape/slice overhead
    width, depth, bs = 16, layers_n, 4
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


def build_resnet_block():
    """Static ResNet bottleneck (1×1 → 3×3 → 1×1 convs, BN, relu,
    shortcut) under Momentum — conv/BN trace cost + fused momentum tail."""
    ch, hw, bs = 8, 6, 2
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


def build_bert_layer():
    """Static transformer layer: QKV projections, scaled-dot attention,
    residual + layer_norm, GELU FFN — fc-heavy, so add+act fusion and the
    Adam tail both engage."""
    hid, seq, heads, bs = 16, 4, 2, 1
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


def build_shared_prompt_work(requests, seed=0):
    """The prefix-cache workload: ONE 12-token system prompt shared by all
    requests, 1-3 token user suffixes — the shape of real assistant
    traffic, and the redundant-prefill worst case. Returns
    ``[(prompt, max_new_tokens), ...]``."""
    rng = np.random.RandomState(seed)
    system = [int(t) for t in rng.randint(3, 120, 12)]
    work = []
    for _ in range(requests):
        suffix = [int(t) for t in rng.randint(3, 120, rng.randint(1, 4))]
        work.append((system + suffix, int(rng.randint(2, 6))))
    return work


REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))


def run_fleet_script(tmp_path, nproc, source, args, timeout=240):
    """Write `source` under `tmp_path` and run it as `nproc` real
    ``jax.distributed`` CPU workers (``fleet_runtime.local_fleet``: gloo
    collectives, one device a process, the full PADDLE_* env). Returns
    ``(exit codes, [the tail of each rank's output])``."""
    from paddle_tpu.fleet_runtime import local_fleet
    script = tmp_path / 'worker.py'
    script.write_text(source)
    outs = []

    def stdout(rank):
        outs.append(open(tmp_path / f'r{rank}.out', 'w'))
        return outs[-1]

    fleet = local_fleet(nproc, script, args=args,
                        env={'PYTHONPATH': REPO, 'PADDLE_TPU_VERIFY': 'off'},
                        stdout=stdout, cwd=REPO)
    try:
        rcs = fleet.wait(timeout=timeout)
    finally:
        for f in outs:
            f.close()
    return rcs, [(tmp_path / f'r{r}.out').read_text()[-2000:]
                 for r in range(nproc)]
