"""BERT pretraining with fleet collective data parallelism.

Usage: python examples/train_bert_fleet.py [--steps N]
Uses all local devices as the 'dp' mesh axis (8 virtual CPU devices under
XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import argparse
import os
import sys

# runnable from anywhere: put the repo root on sys.path
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import dygraph
from paddle_tpu.dygraph.jit import TrainStep
from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    pretrain_loss)
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.mesh import data_sharding


def main():
    import jax
    import jax.numpy as jnp
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=10)
    args = ap.parse_args()
    from paddle_tpu.core.places import on_tpu as _on_tpu
    on_tpu = _on_tpu()

    fleet.init(mesh_shape={'dp': len(jax.devices())})
    cfg = BertConfig.base() if on_tpu else BertConfig.tiny()
    batch = 64 if on_tpu else 8
    seq = 128 if on_tpu else 32

    with dygraph.guard():
        model = BertForPretraining(cfg)
        opt = fluid.optimizer.Adam(1e-4, parameter_list=model.parameters())
        step = TrainStep(model, pretrain_loss, opt,
                         data_sharding=data_sharding(),
                         amp_dtype=jnp.bfloat16 if on_tpu else None)
        rng = np.random.RandomState(0)
        for i in range(args.steps):
            ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype('int64')
            tt = np.zeros((batch, seq), np.int64)
            mlm = np.where(rng.rand(batch, seq) < 0.15,
                           rng.randint(0, cfg.vocab_size, (batch, seq)),
                           -1).astype(np.int64)
            nsp = rng.randint(0, 2, (batch, 1)).astype(np.int64)
            l = step(ids, tt, mlm, nsp)
            print(f"step {i}: loss {float(l):.4f}", flush=True)


if __name__ == '__main__':
    main()
