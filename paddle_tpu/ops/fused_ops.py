"""Fused kernels backing the IR pass pipeline (paddle_tpu/ir/).

Two families, mirroring the reference ops the ``framework/ir`` fusion
passes emit:

- ``fused_elemwise_add_activation`` (ref: fused_elemwise_activation_op.cc)
  — one dispatch for the (bias-add, activation) pair the
  fuse_elewise_add_act pass collapses;
- ``fused_sgd`` / ``fused_momentum`` / ``fused_adam`` — multi-tensor
  apply over ONE flattened parameter bundle (ref: the executables behind
  fuse_all_optimizer_ops). The update arithmetic runs once over the
  bundle, so the jaxpr carries O(#params) cheap reshape/slice equations
  instead of O(#params) copies of the full update chain; Adam's per-param
  bias-correction scalars expand over the bundle with one
  ``jnp.repeat(..., total_repeat_length=)`` gather.

The update math is written expression-for-expression like the per-param
ops in optimizer_ops.py: elementwise arithmetic over a concatenation of
the same values is bit-identical, which the pass-parity suite asserts.

All three bundle ops are update ops (they run after the backward marker,
outside jax.value_and_grad), so they need no custom vjp. Tradeoff,
measured on CPU (PR 3): XLA's backend compile of the bundled
update costs ~5-10% more than N small per-param kernels — paid once EVER
per program via the persistent compile cache (PR 1) — while the trace,
which every cold process pays on every cache hit, shrinks ~1.4×.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .math_ops import _align_y
from .registry import register_op

_ACTS = {'relu': jax.nn.relu, 'sigmoid': jax.nn.sigmoid, 'tanh': jnp.tanh}


@register_op('fused_elemwise_add_activation')
def fused_elemwise_add_activation(x, y, *, functor='relu', axis=-1):
    return _ACTS[functor](jnp.add(jnp.asarray(x), _align_y(x, y, axis)))


# ---------------------------------------------------------------------------
# multi-tensor optimizer apply
# ---------------------------------------------------------------------------

def _bundle(xs):
    """list of arrays → (flat concat, shapes, sizes). Static at trace time;
    1-D members concatenate as-is (ravel would be a no-op equation)."""
    xs = [jnp.asarray(x) for x in xs]
    shapes = [x.shape for x in xs]
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    return (jnp.concatenate([x if x.ndim == 1 else jnp.ravel(x)
                             for x in xs]), shapes, sizes)


def _split(flat, shapes, sizes):
    out, off = [], 0
    for shp, sz in zip(shapes, sizes):
        seg = flat[off:off + sz]
        out.append(seg if shp == (sz,) else jnp.reshape(seg, shp))
        off += sz
    return out


def _per_param(vec, sizes):
    """(N,) per-param scalars → flat (sum(sizes),) vector, each scalar
    repeated over its parameter's span."""
    total = int(sum(sizes))
    return jnp.repeat(vec, np.asarray(sizes), total_repeat_length=total)


@register_op('fused_sgd', outputs=['ParamOut'],
             variadic=['params', 'grads'])
def fused_sgd(params, grads, lr):
    P, shapes, sizes = _bundle(params)
    G, _, _ = _bundle(grads)
    lr = jnp.reshape(jnp.asarray(lr), ())
    return _split(P - lr * G, shapes, sizes)


@register_op('fused_momentum', outputs=['ParamOut', 'VelocityOut'],
             variadic=['params', 'grads', 'velocities'])
def fused_momentum(params, grads, velocities, lr, *, mu=0.9,
                   use_nesterov=False):
    P, shapes, sizes = _bundle(params)
    G, _, _ = _bundle(grads)
    V, _, _ = _bundle(velocities)
    lr = jnp.reshape(jnp.asarray(lr), ())
    v_new = mu * V + G
    if use_nesterov:
        p_new = P - (G + mu * v_new) * lr
    else:
        p_new = P - lr * v_new
    return _split(p_new, shapes, sizes), _split(v_new, shapes, sizes)


@register_op('fused_lars_momentum', outputs=['ParamOut', 'VelocityOut'],
             variadic=['params', 'grads', 'velocities'])
def fused_lars_momentum(params, grads, velocities, lr, *, mu=0.9,
                        lars_coeff=0.001, lars_weight_decay=0.0005,
                        epsilon=0.0):
    """Multi-tensor LARS: the per-LAYER trust ratios are reduced at each
    member's own shape (bitwise-equal to the per-param op's norms), then
    expanded over the bundle so the momentum/update chain runs once over
    the flat concatenation — elementwise, hence bit-identical to N
    separate lars_momentum ops."""
    P, shapes, sizes = _bundle(params)
    G, _, _ = _bundle(grads)
    V, _, _ = _bundle(velocities)
    lr = jnp.reshape(jnp.asarray(lr), ())
    pns = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(p))))
                     for p in params])
    gns = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(g))))
                     for g in grads])
    local_lr = jnp.where(
        (pns > 0) & (gns > 0),
        lr * lars_coeff * pns / (gns + lars_weight_decay * pns + epsilon),
        lr)
    L = _per_param(local_lr, sizes)
    v_new = mu * V + L * (G + lars_weight_decay * P)
    return _split(P - v_new, shapes, sizes), _split(v_new, shapes, sizes)


@register_op('fused_adam', outputs=['ParamOut', 'Moment1Out', 'Moment2Out',
                                    'Beta1PowOut', 'Beta2PowOut'],
             variadic=['params', 'grads', 'moment1s', 'moment2s',
                       'beta1_pows', 'beta2_pows'])
def fused_adam(params, grads, moment1s, moment2s, beta1_pows, beta2_pows,
               lr, *, beta1=0.9, beta2=0.999, epsilon=1e-8):
    P, shapes, sizes = _bundle(params)
    G, _, _ = _bundle(grads)
    M1, _, _ = _bundle(moment1s)
    M2, _, _ = _bundle(moment2s)
    # the _pow slots are (1,)-shaped per param → concatenated they are (N,)
    b1p, _, _ = _bundle(beta1_pows)
    b2p, _, _ = _bundle(beta2_pows)
    lr = jnp.reshape(jnp.asarray(lr), ())
    m1n = beta1 * M1 + (1 - beta1) * G
    m2n = beta2 * M2 + (1 - beta2) * jnp.square(G)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)          # (N,)
    pn = P - _per_param(lr_t, sizes) * m1n / (jnp.sqrt(m2n) + epsilon)
    n = len(sizes)
    pow_shapes, pow_sizes = [(1,)] * n, [1] * n
    return (_split(pn, shapes, sizes), _split(m1n, shapes, sizes),
            _split(m2n, shapes, sizes),
            _split(b1p * beta1, pow_shapes, pow_sizes),
            _split(b2p * beta2, pow_shapes, pow_sizes))
