"""Model compression walkthrough: train a teacher, distill a smaller
student while pruning it, all through the slim Compressor pipeline.

Run: JAX_PLATFORMS=cpu python examples/compress_distill_prune.py
"""
import os
import sys

# runnable from anywhere: put the repo root on sys.path
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np

os.environ.setdefault('XLA_FLAGS',
                      '--xla_force_host_platform_device_count=8')
import paddle_tpu as fluid               # noqa: E402
import paddle_tpu.layers as L            # noqa: E402
from paddle_tpu.contrib import slim      # noqa: E402

BATCH, DIM, CLASSES = 32, 16, 4


def make_batch(rng):
    x = rng.randn(BATCH, DIM).astype('float32')
    y = np.abs(x[:, :CLASSES]).argmax(1)[:, None].astype('int64')
    return x, y


def reader(n, seed):
    rng = np.random.RandomState(seed)

    def r():
        for _ in range(n):
            x, y = make_batch(rng)
            yield {'img': x, 'label': y}
    return r


def build(prefix, width):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data('img', [BATCH, DIM], 'float32')
        y = fluid.data('label', [BATCH, 1], 'int64')
        feat = L.fc(x, size=width, act='relu',
                    param_attr=fluid.ParamAttr(name=prefix + '_w1'))
        logits = L.fc(feat, size=CLASSES,
                      param_attr=fluid.ParamAttr(name=prefix + '_w2'))
        loss = L.reduce_mean(L.softmax_with_cross_entropy(logits, y))
    return prog, startup, feat, logits, loss


def main():
    exe = fluid.Executor(fluid.CPUPlace())

    # 1) teacher: wide net, trained normally
    t_prog, t_start, _, t_logits, t_loss = build('teacher', 64)
    with fluid.program_guard(t_prog, t_start):
        fluid.optimizer.Adam(5e-3).minimize(t_loss)
    exe.run(t_start)
    rng = np.random.RandomState(0)
    for i in range(200):
        x, y = make_batch(rng)
        l, = exe.run(t_prog, feed={'img': x, 'label': y},
                     fetch_list=[t_loss])
    print(f'teacher final loss {float(np.asarray(l)):.4f}')

    # 2) student: half width, distilled + pruned by the Compressor
    s_prog, s_start, _, s_logits, s_loss = build('student', 32)
    exe.run(s_start)
    # soft-label distillation on the logits (same class count either side);
    # the pruning strategy joins at epoch 1 so distillation warms up first
    comp = slim.Compressor(
        place=fluid.CPUPlace(), scope=fluid.global_scope(),
        train_program=slim.GraphWrapper(s_prog,
                                        out_nodes={'loss': s_loss.name}),
        train_reader=reader(30, seed=1),
        teacher_programs=[slim.GraphWrapper(t_prog.clone(for_test=True))],
        distiller_optimizer=fluid.optimizer.Adam(5e-3), epoch=4)
    comp.add_strategy(slim.DistillationStrategy(
        distillers=[slim.SoftLabelDistiller(
            s_logits.name, t_logits.name, teacher_temperature=2.0)],
        start_epoch=0, end_epoch=4))
    comp.add_strategy(slim.UniformPruneStrategy(
        pruner=slim.StructurePruner({'*': 1}, {'*': 'l1_norm'}),
        start_epoch=1, end_epoch=4, target_ratio=0.25,
        params=['student_w1']))
    comp.run()

    w = np.asarray(fluid.global_scope().find('student_w1'))
    pruned_cols = int(np.all(w == 0, axis=0).sum())
    print(f'student trained with distillation; pruned '
          f'{pruned_cols}/{w.shape[1]} filter columns')

    # 3) eval student accuracy on held-out batches
    infer = s_prog.clone(for_test=True)
    rng_ev = np.random.RandomState(9)
    correct = total = 0
    for _ in range(20):
        x, y = make_batch(rng_ev)
        lg, = exe.run(infer, feed={'img': x, 'label': y},
                      fetch_list=[s_logits])
        correct += (np.asarray(lg).argmax(1) == y[:, 0]).sum()
        total += len(y)
    print(f'student accuracy: {correct / total:.3f}')


if __name__ == '__main__':
    main()
