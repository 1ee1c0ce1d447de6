"""ResNet-50 dygraph training with the fused TrainStep (the bench path).

Usage: python examples/train_resnet_dygraph.py [--steps N] [--batch B]
Synthetic data; NHWC + bf16 on TPU."""
import argparse
import os
import sys

# runnable from anywhere: put the repo root on sys.path
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
import time

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.dygraph.jit import TrainStep
from paddle_tpu.dygraph.tape import dispatch_op
from paddle_tpu.models import ResNet50


def main():
    import jax
    import jax.numpy as jnp
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--batch', type=int, default=None)
    args = ap.parse_args()
    from paddle_tpu.core.places import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    batch = args.batch or (128 if on_tpu else 4)
    img = 224 if on_tpu else 32
    fmt = 'NHWC' if on_tpu else 'NCHW'

    with dygraph.guard():
        model = ResNet50(class_dim=1000, data_format=fmt)
        opt = fluid.optimizer.Momentum(0.1, momentum=0.9,
                                       parameter_list=model.parameters())

        def loss_fn(m, x, y):
            logits = dispatch_op('cast', {'x': m(x)}, {'dtype': 'float32'})
            l, _ = dispatch_op('softmax_with_cross_entropy',
                               {'logits': logits, 'label': y}, {})
            return dispatch_op('reduce_mean', {'x': l}, {})

        step = TrainStep(model, loss_fn, opt,
                         amp_dtype=jnp.bfloat16 if on_tpu else None)
        shape = (batch, img, img, 3) if fmt == 'NHWC' else (batch, 3, img, img)
        x = np.random.randn(*shape).astype(np.float32)
        y = np.random.randint(0, 1000, (batch, 1)).astype(np.int64)
        if on_tpu:
            # keep the synthetic batch device-resident (a real input
            # pipeline overlaps transfers via the DataLoader ring)
            x = jnp.asarray(x, jnp.bfloat16)
        l = step(x, y)                        # compile
        float(l)
        t0 = time.perf_counter()
        for i in range(args.steps):
            l = step(x, y)
        print(f"loss {float(l):.4f}  "
              f"{batch * max(args.steps, 1) / (time.perf_counter() - t0):.1f}"
              f" img/s")


if __name__ == '__main__':
    main()
