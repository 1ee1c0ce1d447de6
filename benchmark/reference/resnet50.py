"""Plain reference for resnet50: ResNet-50 v1 (He et al. 2015,
arXiv:1512.03385, table 1) forward, softmax cross-entropy, gradients and one
Momentum update, in float32 jax.numpy at precision "highest". No kernels, no
mixed precision, no framework code.

Follows the paper's bottleneck layout: 7x7/2 stem, 3x3/2 max pool, stages of
3, 4, 6, 3 bottlenecks (1x1, 3x3, 1x1 with x4 expansion), a projection
shortcut on the first block of each stage, global average pool, one linear
layer. Departures, both of the program's model (v1.5, as in every current
ResNet-50 recipe): the stride of a down-sampling block sits on its 3x3
convolution, not its first 1x1; batch norm uses the batch's statistics with
epsilon 1e-5.

Weights arrive under the program's parameter names (NHWC activations, HWIO
kernels); nothing else is taken from the program.

A bottleneck is rematerialised in the backward pass (jax.checkpoint): the
float32 activations of 128 images would not fit the chip, and the
reference's peak memory has to stay under the program's, or the device's
peak_bytes_in_use would report the reference (4.5 GB against 4.8 GB by the
compiler's memory analysis for the v5e). The arithmetic is unchanged.
"""
import jax
import jax.numpy as jnp
from jax import lax

STAGES = {50: (3, 4, 6, 3)}
HIGHEST = lax.Precision.HIGHEST


def _conv(x, w, stride):
    pad = (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)


def _conv_bn(p, prefix, x, stride=1, relu=True):
    y = _conv(x, p[prefix + '._conv.weight'], stride)
    mean = jnp.mean(y, (0, 1, 2))
    var = jnp.var(y, (0, 1, 2))
    y = (y - mean) * lax.rsqrt(var + 1e-5) * p[prefix + '._bn.weight'] \
        + p[prefix + '._bn.bias']
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, prefix, x, stride, project):
    y = _conv_bn(p, prefix + '.conv0', x)
    y = _conv_bn(p, prefix + '.conv1', y, stride)
    y = _conv_bn(p, prefix + '.conv2', y, relu=False)
    if project:
        x = _conv_bn(p, prefix + '.short', x, stride, relu=False)
    return jax.nn.relu(x + y)


def logits(p, image, depth=50):
    x = _conv_bn(p, 'conv', image.astype(jnp.float32), 2)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    i = 0
    for stage, blocks in enumerate(STAGES[depth]):
        for b in range(blocks):
            stride = 2 if b == 0 and stage > 0 else 1
            block = jax.checkpoint(_bottleneck, static_argnums=(1, 3, 4))
            x = block(p, f'blocks.{i}', x, stride, b == 0)
            i += 1
    x = jnp.mean(x, (1, 2))
    return jnp.matmul(x, p['out.weight'], precision=HIGHEST) + p['out.bias']


def loss(p, image, label, depth=50):
    logp = jax.nn.log_softmax(logits(p, image, depth))
    return -jnp.mean(jnp.take_along_axis(logp, label.reshape(-1, 1), 1))


def losses(config, params, batch):
    """{'loss0', 'loss1'}: the loss of the batch at the given weights, and
    after one Momentum update from its gradients. The second depends on
    every gradient and on the update: a step that skipped it would repeat
    the first. Batch norm couples the batch, so it is taken whole."""
    o, depth = config['optimizer'], config['model']['depth']

    @jax.jit
    def run(p, b):
        l0, g = jax.value_and_grad(loss)(p, *b, depth)
        # Momentum from zero velocity: v = g, p <- p - lr v
        new = jax.tree_util.tree_map(
            lambda w, d: w - o['learning_rate'] * d, p, g)
        return l0, loss(new, *b, depth)

    l0, l1 = run(params, batch)
    return {'loss0': float(l0), 'loss1': float(l1)}
