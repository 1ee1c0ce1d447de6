"""ResNet large-batch training with LARS — the TPU-v3-pod recipe of
"Scale MLPerf-0.6 models on Google TPU-v3 Pods" (arXiv 1909.09756 §2):
LARS with per-layer trust ratios, linear LR warmup into polynomial decay,
weight decay excluded for biases and batch-norm scale/shift, sync-BN over
the data axes, and per-host input sharding when run as a fleet.

Single host::

    python examples/train_resnet_lars.py [--steps N] [--batch B]

As a local test fleet (2 real jax.distributed CPU workers)::

    python examples/train_resnet_lars.py --nproc 2
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
import time

import numpy as np


def is_bn_or_bias(param):
    """The standard LARS exclusion set: biases and norm scale/shift train
    WITHOUT weight decay in their trust-ratio denominators."""
    name = getattr(param, 'name', str(param))
    return any(m in name for m in ('.b_0', 'bias', 'bn', 'batch_norm',
                                   '.w_1', 'scale', 'offset'))


def main():
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import layers as L

    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--batch', type=int, default=None,
                    help='GLOBAL batch (split across hosts)')
    ap.add_argument('--nproc', type=int, default=0,
                    help='spawn N local jax.distributed CPU workers')
    args = ap.parse_args()

    if args.nproc:
        # re-exec self as a local fleet (fleet_runtime.local_fleet wires
        # PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ID / endpoints)
        from paddle_tpu.fleet_runtime import local_fleet
        fl = local_fleet(args.nproc, os.path.abspath(__file__),
                         args=['--steps', args.steps]
                         + (['--batch', args.batch] if args.batch else []))
        rcs = fl.wait()
        sys.exit(max(rc if rc is not None else 1 for rc in rcs))

    from paddle_tpu.fleet_runtime import bootstrap
    bootstrap()                       # no-op single-host; fleet env wires up
    from paddle_tpu.core.places import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    hosts = jax.process_count()
    global_batch = args.batch or (256 if on_tpu else 16)
    img = 64 if on_tpu else 16

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        x = L.data('image', shape=[3, img, img], dtype='float32')
        y = L.data('label', shape=[1], dtype='int64')
        h = L.conv2d(x, num_filters=16, filter_size=3, padding=1)
        # sync-BN: batch statistics reduced over the partitioner's data
        # axes, so per-host stats equal the single-host global-batch stats
        h = L.batch_norm(h, act='relu', sync_stats=True)
        h = L.pool2d(h, pool_size=2, pool_type='max', pool_stride=2)
        h = L.conv2d(h, num_filters=32, filter_size=3, padding=1)
        h = L.batch_norm(h, act='relu', sync_stats=True)
        h = L.pool2d(h, pool_size=2, pool_type='avg',
                     global_pooling=True)
        logits = L.fc(h, size=10)
        loss = L.mean(L.softmax_with_cross_entropy(logits, y))

        # the large-batch schedule: linear warmup into polynomial decay
        base_lr = 0.1 * (global_batch / 256.0)     # linear scaling rule
        lr = L.linear_lr_warmup(
            L.polynomial_decay(base_lr, decay_steps=max(args.steps, 10),
                               end_learning_rate=1e-4, power=2.0),
            warmup_steps=max(args.steps // 10, 2),
            start_lr=0.0, end_lr=base_lr)
        opt = fluid.optimizer.LarsMomentumOptimizer(
            lr, momentum=0.9, lars_coeff=0.001, lars_weight_decay=5e-4,
            exclude_from_weight_decay_fn=is_bn_or_bias)
        from paddle_tpu.parallel import DistributedStrategy, fleet
        fleet.init()
        fleet.distributed_optimizer(opt,
                                    strategy=DistributedStrategy()) \
            .minimize(loss)

    exe = fluid.Executor()
    exe.run(startup)

    blk = main_prog.global_block()
    loader = fluid.DataLoader.from_generator(
        feed_list=[blk.var('image'), blk.var('label')], capacity=4)
    # each host reads only its process_index-strided rows of every batch
    loader.shard_for_fleet()

    def batches():
        rng = np.random.RandomState(0)
        for _ in range(args.steps):
            yield (rng.randn(global_batch, 3, img, img).astype('float32'),
                   rng.randint(0, 10, (global_batch, 1)).astype('int64'))

    loader.set_batch_generator(batches)

    t0, last = time.perf_counter(), None
    n = 0
    for batch in loader():
        last = float(np.asarray(
            exe.run(main_prog, feed=batch, fetch_list=[loss])[0]))
        n += 1
    dt = time.perf_counter() - t0
    if jax.process_index() == 0:
        print(f'host 0/{hosts}: {n} steps, final loss {last:.4f}, '
              f'{n / dt:.2f} steps/s (global batch {global_batch})')


if __name__ == '__main__':
    main()
