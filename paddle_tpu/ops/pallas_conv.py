"""TPU conv-efficiency kernels (ROADMAP S5):

1. `stem_space_to_depth` — the 7×7/s2 ResNet stem re-laid-out as a 4×4/s1
   conv on a 2×2 space-to-depth grid (input 224×224×3 → 112×115×12-ish).
   Bit-for-bit the same dot products, but the MXU sees 12 input channels
   instead of 3 and a stride-1 window instead of stride-2 — the standard
   MLPerf-class ResNet stem optimization, expressed in pure XLA ops.

2. `fused_conv1x1_bn_act` — pallas kernel fusing a 1×1 conv (a matmul on
   the MXU) with the BatchNorm affine and activation in the epilogue, so
   the conv output never round-trips to HBM between conv and BN. 1×1 convs
   are ~45% of ResNet-50's conv FLOPs (all bottleneck reduce/expand convs).
   Off-TPU the op is the equivalent XLA form.

Measured decisions pend a chip run (tools/bench_fused_conv.py is the
harness); both paths are exact-parity tested against the reference
formulation on CPU (pallas interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op
from ..core.places import on_tpu


# ---------------------------------------------------------------------------
# space-to-depth stem
# ---------------------------------------------------------------------------

@register_op('conv2d_stem_s2d')
def stem_space_to_depth(x, weight, *, data_format='NHWC'):
    """Equivalent of conv2d(x, weight, stride=2, padding=3) for a 7×7 HWIO
    `weight` (the NHWC conv weight layout), NHWC `x` — via 2×2
    space-to-depth.

    Derivation (per spatial axis): y[i] = Σ_{k=0..7} xp[2i+k]·w8[k] with
    xp = pad(x, (4, 2)) and w8 = [0, w0..w6] (zero tap in FRONT aligns the
    even grid: pad-left 4 = original pad 3 + the shift the zero tap
    absorbs). Writing k = 2t+r splits the sum over the s2d channel r and a
    4-tap stride-1 window t on the half-resolution grid.
    """
    if data_format != 'NHWC':
        raise ValueError('stem_space_to_depth requires NHWC')
    x = jnp.asarray(x)
    w = jnp.asarray(weight)           # HWIO, 7×7
    if w.shape[:2] != (7, 7):
        raise ValueError(f'stem kernel must be 7x7 HWIO, got {w.shape}')
    from .nn_ops import _match_weight_dtype
    x = _match_weight_dtype(x, w)     # same AMP rule as conv2d: x → w.dtype
    n, h, hw, c = x.shape
    o = w.shape[-1]
    # zero tap in front → 8×8, then split even/odd taps
    w8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
    # W2[tH, tW, rH·2C + rW·C + c, o] = w8[2tH+rH, 2tW+rW, c, o]
    w2 = w8.reshape(4, 2, 4, 2, c, o)
    w2 = w2.transpose(0, 2, 1, 3, 4, 5)          # tH tW rH rW c o
    w2 = w2.reshape(4, 4, 4 * c, o)              # HWIO, I = rH·rW·c packed
    # output size of conv(k=7, s=2, p=3); padded length 2·out+6 keeps the
    # last window in range and the s2d grid even for any input parity
    h_out, w_out = (h - 1) // 2 + 1, (hw - 1) // 2 + 1
    pad_h, pad_w = 2 * h_out + 2 - h, 2 * w_out + 2 - hw
    xp = jnp.pad(x, ((0, 0), (4, pad_h), (4, pad_w), (0, 0)))
    h2, w2dim = h_out + 3, w_out + 3
    xs = xp.reshape(n, h2, 2, w2dim, 2, c).transpose(0, 1, 3, 2, 4, 5)
    xs = xs.reshape(n, h2, w2dim, 4 * c)         # channel = rH·2C + rW·C + c
    dn = jax.lax.conv_dimension_numbers(xs.shape, w2.shape,
                                        ('NHWC', 'HWIO', 'NHWC'))
    return jax.lax.conv_general_dilated(
        xs, w2, window_strides=(1, 1), padding='VALID',
        dimension_numbers=dn,
        preferred_element_type=x.dtype if x.dtype == jnp.float32 else None)


# ---------------------------------------------------------------------------
# pallas fused 1×1 conv + BN affine + activation
# ---------------------------------------------------------------------------

def _fused_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, *, act):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    y = acc * scale_ref[...] + shift_ref[...]
    if act == 'relu':
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def _pallas_matmul_affine(x2d, w, scale, shift, act, out_dtype,
                          interpret=False, bm=256, bn=128):
    from jax.experimental import pallas as pl
    m, k = x2d.shape
    ko, n = w.shape
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_fused_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(x2d, w, scale.reshape(1, -1), shift.reshape(1, -1))


@register_op('fused_conv1x1_bn_act')
def fused_conv1x1_bn_act(x, weight, scale, shift, *, act=None,
                         data_format='NHWC', force_pallas=None):
    """out = act((x ⊛ weight) * scale + shift) for a 1×1 HWIO weight (the
    NHWC conv weight layout), NHWC x. scale/shift are the folded BN affine
    (γ/√(σ²+ε), β − μ·that) — inference mode, or training mode after the
    stats pass.

    TPU: one pallas matmul with the affine+act in the epilogue (the conv
    output never hits HBM unnormalized). Elsewhere: the same math in XLA.
    """
    x = jnp.asarray(x)
    w = jnp.asarray(weight)
    if data_format != 'NHWC':
        raise ValueError('fused_conv1x1_bn_act requires NHWC')
    if w.shape[:2] != (1, 1):
        raise ValueError(f'kernel must be 1x1 HWIO, got {w.shape}')
    from .nn_ops import _match_weight_dtype
    x = _match_weight_dtype(x, w)     # same AMP rule as conv2d: x → w.dtype
    scale = jnp.asarray(scale, x.dtype)
    shift = jnp.asarray(shift, x.dtype)
    n, h, hw, c = x.shape
    o = w.shape[-1]
    w2d = w.reshape(c, o)                         # (C, O)
    # the kernel runs on a TPU, or anywhere (pallas interpret mode off-chip)
    # when a test or bench asks for it by name; either way a kernel that
    # does not lower FAILS instead of measuring/verifying the XLA form
    use_pallas = on_tpu() if force_pallas is None else force_pallas
    if use_pallas:
        y = _pallas_matmul_affine(
            x.reshape(-1, c), w2d, scale, shift, act, x.dtype,
            interpret=not on_tpu())
        return y.reshape(n, h, hw, o)
    y = jnp.einsum('nhwc,co->nhwo', x, w2d) * scale + shift
    if act == 'relu':
        y = jnp.maximum(y, 0.0)
    return y
