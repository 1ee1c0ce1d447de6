"""Functional op library. Importing this package registers all ops.

Modules double as a direct functional API (used by dygraph layers), e.g.
`from paddle_tpu.ops import nn_ops as F; F.conv2d(x, w, stride=1)`.
"""
from . import registry
from .registry import register_op, get_op, has_op, all_ops, custom_op
from . import (math_ops, tensor_ops, nn_ops, loss_ops, random_ops,
               optimizer_ops, extra_ops, rnn_ops, sequence_ops, vision_ops,
               detection_ops, quant_ops, contrib_ops, pallas_conv, fused_ops,
               sparse_ops, llm_ops)

# collective ops live in parallel/collective.py (jax collectives usable
# inside shard_map programs), not in this registry.
