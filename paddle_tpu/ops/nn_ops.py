"""Neural-network ops: conv, pool, normalization, embedding, dropout, resize.

Parity targets: reference paddle/fluid/operators/{conv,pool,batch_norm,
layer_norm,group_norm,instance_norm,data_norm,dropout,lookup_table,softmax,
lrn,interpolate,grid_sampler,affine_grid,pixel_shuffle,unfold,im2sequence,
row_conv,bilinear_tensor_product}_op.* — implemented as jax functionals on
lax.conv_general_dilated / reduce_window so XLA tiles them onto the MXU.
Layouts: Paddle default NCHW is honored; NHWC supported via data_format attr
(preferred on TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_group_read import group_read, group_read_kernel_applies
from .registry import register_op
from ..core.dtypes import to_jax_dtype
from ..core.places import on_tpu


def _pair(v, n=2):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * n


def _match_weight_dtype(x, w):
    """AMP harmonization: an fp32 activation meeting a low-precision
    weight computes in the WEIGHT's dtype (the master-weight design casts
    params to the compute dtype; feeds may still arrive fp32)."""
    if (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(w.dtype, jnp.floating)
            and x.dtype != w.dtype):
        return x.astype(w.dtype)
    return x


def _conv_dims(data_format, nd):
    if nd == 2:
        return ('NCHW', 'OIHW', 'NCHW') if data_format == 'NCHW' else ('NHWC', 'HWIO', 'NHWC')
    return ('NCDHW', 'OIDHW', 'NCDHW') if data_format == 'NCDHW' else ('NDHWC', 'DHWIO', 'NDHWC')


@register_op('conv2d')
def conv2d(x, weight, *, stride=1, padding=0, dilation=1, groups=1,
           data_format='NCHW'):
    """ref: paddle/fluid/operators/conv_op.cc (weights always OIHW)."""
    x = jnp.asarray(x)
    w = jnp.asarray(weight)
    x = _match_weight_dtype(x, w)
    stride = _pair(stride)
    dilation = _pair(dilation)
    if isinstance(padding, str):
        pad = padding.upper()  # 'SAME' | 'VALID'
    else:
        p = _pair(padding)
        pad = [(p[0], p[0]), (p[1], p[1])] if len(p) == 2 else \
            [(p[0], p[1]), (p[2], p[3])]
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims(data_format, 2))
    return lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=x.dtype if x.dtype == jnp.float32 else None)


@register_op('conv3d')
def conv3d(x, weight, *, stride=1, padding=0, dilation=1, groups=1,
           data_format='NCDHW'):
    x = jnp.asarray(x)
    w = jnp.asarray(weight)
    x = _match_weight_dtype(x, w)
    stride = _pair(stride, 3)
    dilation = _pair(dilation, 3)
    p = _pair(padding, 3)
    pad = [(pi, pi) for pi in p] if not isinstance(padding, str) else padding.upper()
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims(data_format, 3))
    return lax.conv_general_dilated(x, w, stride, pad, rhs_dilation=dilation,
                                    dimension_numbers=dn, feature_group_count=groups)


@register_op('conv2d_transpose')
def conv2d_transpose(x, weight, *, stride=1, padding=0, dilation=1, groups=1,
                     output_size=None, data_format='NCHW'):
    """ref: paddle/fluid/operators/conv_transpose_op.cc. Weight layout IOHW."""
    x = jnp.asarray(x)
    w = jnp.asarray(weight)
    x = _match_weight_dtype(x, w)
    stride = _pair(stride)
    p = _pair(padding)
    # grad-of-conv formulation: lhs_dilation = stride
    k = (w.shape[2], w.shape[3])
    pad = [(dilation * (k[0] - 1) - p[0], dilation * (k[0] - 1) - p[0]),
           (dilation * (k[1] - 1) - p[1], dilation * (k[1] - 1) - p[1])]
    if data_format == 'NCHW':
        dims = ('NCHW', 'OIHW', 'NCHW')
    else:
        dims = ('NHWC', 'HWIO', 'NHWC')
    if groups > 1:
        ci = w.shape[0]
        w = w.reshape(groups, ci // groups, *w.shape[1:]).transpose(0, 2, 1, 3, 4) \
            .reshape(-1, ci // groups, *w.shape[2:])
    else:
        w = jnp.swapaxes(w, 0, 1)  # IOHW -> OIHW
    w = jnp.flip(w, axis=(-2, -1))
    dn = lax.conv_dimension_numbers(x.shape, w.shape, dims)
    return lax.conv_general_dilated(x, w, window_strides=(1, 1), padding=pad,
                                    lhs_dilation=stride, rhs_dilation=_pair(dilation),
                                    dimension_numbers=dn, feature_group_count=groups)


@register_op('conv3d_transpose')
def conv3d_transpose(x, weight, *, stride=1, padding=0, dilation=1, groups=1,
                     data_format='NCDHW'):
    x = jnp.asarray(x)
    w = jnp.asarray(weight)
    x = _match_weight_dtype(x, w)
    stride = _pair(stride, 3)
    p = _pair(padding, 3)
    d = _pair(dilation, 3)
    k = w.shape[2:]
    pad = [(d[i] * (k[i] - 1) - p[i],) * 2 for i in range(3)]
    w = jnp.swapaxes(w, 0, 1)
    w = jnp.flip(w, axis=(-3, -2, -1))
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ('NCDHW', 'OIDHW', 'NCDHW'))
    return lax.conv_general_dilated(x, w, (1, 1, 1), pad, lhs_dilation=stride,
                                    rhs_dilation=d, dimension_numbers=dn,
                                    feature_group_count=groups)


def _pool(x, ksize, stride, padding, pool_type, nd, ceil_mode=False,
          exclusive=True, data_format='NCHW', global_pool=False):
    x = jnp.asarray(x)
    spatial = tuple(range(2, 2 + nd)) if data_format.startswith('NC') else tuple(range(1, 1 + nd))
    if global_pool:
        ksize = [x.shape[a] for a in spatial]
        stride = ksize
        padding = [0] * nd
    ksize = _pair(ksize, nd)
    stride = _pair(stride if stride is not None else ksize, nd)
    p = _pair(padding, nd)
    window = [1] * x.ndim
    strides = [1] * x.ndim
    pads = [(0, 0)] * x.ndim
    for i, a in enumerate(spatial):
        window[a] = ksize[i]
        strides[a] = stride[i]
        extra = 0
        if ceil_mode:
            size = x.shape[a]
            rem = (size + 2 * p[i] - ksize[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
        pads[a] = (p[i], p[i] + extra)
    import numpy as np
    if pool_type == 'max':
        # init must stay a concrete literal: a traced constant breaks the
        # select-and-scatter grad rule under jit-of-grad
        init = np.array(-np.inf if jnp.issubdtype(x.dtype, jnp.floating)
                        else np.iinfo(x.dtype).min, x.dtype)
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    # avg
    ones = jnp.ones_like(x)
    zero = np.array(0, x.dtype)
    s = lax.reduce_window(x, zero, lax.add, window, strides, pads)
    if exclusive:
        cnt = lax.reduce_window(ones, zero, lax.add, window, strides, pads)
    else:
        cnt = np.array(math.prod(ksize), x.dtype)
    return s / cnt


@register_op('pool2d')
def pool2d(x, *, pool_size=-1, pool_type='max', pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, exclusive=True,
           data_format='NCHW'):
    """ref: paddle/fluid/operators/pool_op.cc."""
    return _pool(x, pool_size, pool_stride, pool_padding, pool_type, 2,
                 ceil_mode, exclusive, data_format, global_pooling)


@register_op('pool3d')
def pool3d(x, *, pool_size=-1, pool_type='max', pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, exclusive=True,
           data_format='NCDHW'):
    return _pool(x, pool_size, pool_stride, pool_padding, pool_type, 3,
                 ceil_mode, exclusive, data_format, global_pooling)


@register_op('adaptive_pool2d')
def adaptive_pool2d(x, *, pool_size, pool_type='max'):
    """ref: adaptive pooling in paddle/fluid/operators/pool_op.cc (adaptive=True).
    Requires divisible spatial dims (true for all ref model configs)."""
    x = jnp.asarray(x)
    n, c, h, w = x.shape
    oh, ow = _pair(pool_size)
    x = x.reshape(n, c, oh, h // oh, ow, w // ow)
    if pool_type == 'max':
        return jnp.max(x, axis=(3, 5))
    return jnp.mean(x, axis=(3, 5))


@register_op('adaptive_pool3d')
def adaptive_pool3d(x, *, pool_size, pool_type='max'):
    x = jnp.asarray(x)
    n, c, d, h, w = x.shape
    od, oh, ow = _pair(pool_size, 3)
    x = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow)
    if pool_type == 'max':
        return jnp.max(x, axis=(3, 5, 7))
    return jnp.mean(x, axis=(3, 5, 7))


@register_op('softmax')
def softmax(x, *, axis=-1):
    return jax.nn.softmax(jnp.asarray(x), axis=axis)


@register_op('log_softmax')
def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(jnp.asarray(x), axis=axis)


def _bound_sync_axes():
    """Mesh axes batch stats reduce over for sync-BN: the partitioner's
    data axes that are LIVE in the surrounding trace (shard_map). On the
    GSPMD executor no axis is bound — and none is needed: jnp.mean over
    the globally-sharded batch already reduces over every shard, so
    sync_stats is the identity there by construction."""
    from ..parallel.collective import _axis_bound
    from ..partition import get_partitioner
    return tuple(a for a in (get_partitioner().data_axes() or ())
                 if _axis_bound(a))


@register_op('batch_norm', outputs=['Y', 'MeanOut', 'VarianceOut'])
def batch_norm(x, scale, bias, mean, variance, *, momentum=0.9, epsilon=1e-5,
               is_test=False, use_global_stats=False, data_layout='NCHW',
               sync_stats=False):
    """ref: paddle/fluid/operators/batch_norm_op.cc. Returns (y, new_running_
    mean, new_running_var); the graph aliases MeanOut/VarianceOut onto the
    input stat vars so the lowered step updates state functionally.

    ``sync_stats`` (the reference's sync_batch_norm, arXiv 1909.09756's
    large-batch ingredient): batch mean/variance are reduced over the
    partitioner's data axes, so every shard normalizes with GLOBAL-batch
    statistics — mean via pmean of per-shard means (equal shard sizes),
    variance via the E[x²]−E[x]² decomposition over the same reductions.
    Under explicit SPMD (shard_map) this emits real collectives; on the
    GSPMD executor the plain batch reduction is already global."""
    x = jnp.asarray(x)
    scale = jnp.asarray(scale)
    bias = jnp.asarray(bias)
    mean = jnp.asarray(mean)
    variance = jnp.asarray(variance)
    if data_layout == 'NCHW' and x.ndim > 2:
        axes = (0,) + tuple(range(2, x.ndim))
        shape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        axes = tuple(range(x.ndim - 1))
        shape = (1,) * (x.ndim - 1) + (-1,)
    if is_test or use_global_stats:
        m, v = mean, variance
        new_mean, new_var = mean, variance
    else:
        xf = x.astype(jnp.float32)
        sync_axes = _bound_sync_axes() if sync_stats else ()
        if sync_axes:
            m = lax.pmean(jnp.mean(xf, axes), sync_axes)
            ex2 = lax.pmean(jnp.mean(jnp.square(xf), axes), sync_axes)
            v = ex2 - jnp.square(m)
        else:
            m = jnp.mean(xf, axes)
            v = jnp.var(xf, axes)
        new_mean = momentum * mean + (1 - momentum) * m.astype(mean.dtype)
        new_var = momentum * variance + (1 - momentum) * v.astype(variance.dtype)
        new_mean = lax.stop_gradient(new_mean)
        new_var = lax.stop_gradient(new_var)
    inv = lax.rsqrt(v.astype(jnp.float32) + epsilon).astype(x.dtype)
    y = (x - m.astype(x.dtype).reshape(shape)) * inv.reshape(shape) \
        * scale.reshape(shape) + bias.reshape(shape)
    return y, new_mean, new_var


@register_op('layer_norm')
def layer_norm(x, scale=None, bias=None, *, begin_norm_axis=1, epsilon=1e-5):
    """ref: paddle/fluid/operators/layer_norm_op.cc."""
    x = jnp.asarray(x)
    axes = tuple(range(begin_norm_axis, x.ndim))
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axes, keepdims=True)
    v = jnp.var(xf, axes, keepdims=True)
    y = ((xf - m) * lax.rsqrt(v + epsilon)).astype(x.dtype)
    norm_shape = x.shape[begin_norm_axis:]
    if scale is not None:
        y = y * jnp.asarray(scale).reshape(norm_shape)
    if bias is not None:
        y = y + jnp.asarray(bias).reshape(norm_shape)
    return y


@register_op('instance_norm')
def instance_norm(x, scale=None, bias=None, *, epsilon=1e-5):
    """ref: paddle/fluid/operators/instance_norm_op.cc (NCHW)."""
    x = jnp.asarray(x)
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axes, keepdims=True)
    v = jnp.var(x, axes, keepdims=True)
    y = (x - m) * lax.rsqrt(v + epsilon)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * jnp.asarray(scale).reshape(shape)
    if bias is not None:
        y = y + jnp.asarray(bias).reshape(shape)
    return y


@register_op('group_norm')
def group_norm(x, scale=None, bias=None, *, groups, epsilon=1e-5,
               data_layout='NCHW'):
    """ref: paddle/fluid/operators/group_norm_op.cc."""
    x = jnp.asarray(x)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = x.reshape(n, groups, c // groups, *spatial)
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axes, keepdims=True)
    v = jnp.var(xg, axes, keepdims=True)
    y = ((xg - m) * lax.rsqrt(v + epsilon)).reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * jnp.asarray(scale).reshape(shape)
    if bias is not None:
        y = y + jnp.asarray(bias).reshape(shape)
    return y


@register_op('data_norm', outputs=['Y', 'BatchSizeOut', 'BatchSumOut', 'BatchSquareSumOut'])
def data_norm(x, batch_size, batch_sum, batch_square_sum, *, epsilon=1e-4,
              is_test=False):
    """ref: paddle/fluid/operators/data_norm_op.cc (CTR models)."""
    x = jnp.asarray(x)
    bsize = jnp.asarray(batch_size)
    bsum = jnp.asarray(batch_sum)
    bsq = jnp.asarray(batch_square_sum)
    mean = bsum / bsize
    scale = jnp.sqrt(bsize / (bsq - bsum * bsum / bsize + epsilon))
    y = (x - mean) * scale
    if is_test:
        return y, bsize, bsum, bsq
    n = jnp.asarray(x.shape[0], bsize.dtype)
    nb = lax.stop_gradient(bsize + n)
    ns = lax.stop_gradient(bsum + jnp.sum(x, 0))
    nq = lax.stop_gradient(bsq + jnp.sum(jnp.square(x), 0))
    return y, nb, ns, nq


@register_op('dropout', needs_rng=True)
def dropout(x, *, dropout_prob=0.5, is_test=False,
            dropout_implementation='downgrade_in_infer', key=None):
    """ref: paddle/fluid/operators/dropout_op.cc. Both paddle semantics:
    downgrade_in_infer (scale at infer) and upscale_in_train."""
    x = jnp.asarray(x)
    if is_test:
        if dropout_implementation == 'downgrade_in_infer':
            return x * (1.0 - dropout_prob)
        return x
    if dropout_prob == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - dropout_prob, x.shape)
    if dropout_implementation == 'upscale_in_train':
        return jnp.where(keep, x / (1.0 - dropout_prob), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


@register_op('lookup_table')
def lookup_table(w, ids, *, padding_idx=-1, is_sparse=False,
                 is_distributed=False, _sparse_site=None):
    """Embedding lookup (ref: paddle/fluid/operators/lookup_table_op.cc).

    ``is_sparse=True`` + a bound ``_sparse_site`` (the static sparse-grad
    path, docs/SPARSE.md): the gathered rows add a zero-valued surrogate
    from the trace context (exact: +0.0), so the backward produces the
    per-occurrence row cotangent O(nnz·D) instead of the dense V×D
    scatter — the table itself is a non-differentiated constant in that
    mode. Outside a sparse trace (eval clones, inference programs,
    PADDLE_TPU_SPARSE_GRAD=0) the surrogate resolves to None and this is
    the plain dense gather."""
    w = jnp.asarray(w)
    ids = jnp.asarray(ids)
    squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
    if squeeze_last:
        ids = ids[..., 0]
    surrogate = None
    if _sparse_site is not None:
        from .sparse_ops import site_value
        surrogate = site_value(_sparse_site)
    if surrogate is not None:
        w = lax.stop_gradient(w)
    out = jnp.take(w, jnp.clip(ids, 0, w.shape[0] - 1), axis=0)
    if surrogate is not None:
        out = out + surrogate.reshape(out.shape)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return out


@register_op('lrn')
def lrn(x, *, n=5, k=1.0, alpha=1e-4, beta=0.75):
    """ref: paddle/fluid/operators/lrn_op.cc (NCHW)."""
    x = jnp.asarray(x)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    window = [1, n, 1, 1]
    import numpy as np
    s = lax.reduce_window(pad, np.array(0, x.dtype), lax.add, window,
                          [1, 1, 1, 1], [(0, 0)] * 4)
    return x / jnp.power(k + alpha * s, beta)


@register_op('interpolate')
def interpolate(x, *, out_shape, method='bilinear', align_corners=True,
                align_mode=1, data_format='NCHW'):
    """ref: paddle/fluid/operators/interpolate_op.cc (bilinear/nearest/trilinear)."""
    x = jnp.asarray(x)
    if data_format == 'NCHW' or data_format == 'NCDHW':
        spatial_start = 2
    else:
        spatial_start = 1
    in_sp = x.shape[spatial_start:spatial_start + len(out_shape)]
    out_sp = tuple(int(s) for s in out_shape)

    def src_idx(out_len, in_len):
        i = jnp.arange(out_len, dtype=jnp.float32)
        if method == 'nearest':
            if align_corners:
                return jnp.round(i * (in_len - 1) / max(out_len - 1, 1))
            return jnp.floor(i * in_len / out_len)
        if align_corners:
            return i * (in_len - 1) / max(out_len - 1, 1)
        if align_mode == 0:
            return jnp.clip((i + 0.5) * in_len / out_len - 0.5, 0, in_len - 1)
        return jnp.clip(i * in_len / out_len, 0, in_len - 1)

    if method == 'nearest':
        out = x
        for d, (ol, il) in enumerate(zip(out_sp, in_sp)):
            idx = src_idx(ol, il).astype(jnp.int32)
            out = jnp.take(out, idx, axis=spatial_start + d)
        return out
    # (bi/tri)linear: separable 1-D lerps
    out = x.astype(jnp.float32)
    for d, (ol, il) in enumerate(zip(out_sp, in_sp)):
        axis = spatial_start + d
        si = src_idx(ol, il)
        lo = jnp.floor(si).astype(jnp.int32)
        hi = jnp.clip(lo + 1, 0, il - 1)
        w = (si - lo).astype(out.dtype)
        a = jnp.take(out, lo, axis=axis)
        b = jnp.take(out, hi, axis=axis)
        shape = [1] * out.ndim
        shape[axis] = ol
        w = w.reshape(shape)
        out = a * (1 - w) + b * w
    return out.astype(x.dtype)


@register_op('pixel_shuffle')
def pixel_shuffle(x, *, upscale_factor):
    x = jnp.asarray(x)
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(n, c // (r * r), h * r, w * r)


@register_op('unfold')
def unfold(x, *, kernel_sizes, strides=1, paddings=0, dilations=1):
    """im2col (ref: paddle/fluid/operators/unfold_op.cc)."""
    x = jnp.asarray(x)
    n, c, h, w = x.shape
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    xp = jnp.pad(x, [(0, 0), (0, 0), (ph, ph), (pw, pw)])
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(lax.slice(
                xp, (0, 0, i * dh, j * dw),
                (n, c, i * dh + (oh - 1) * sh + 1, j * dw + (ow - 1) * sw + 1),
                (1, 1, sh, sw)))
    col = jnp.stack(patches, 2)  # n, c, kh*kw, oh, ow
    return col.reshape(n, c * kh * kw, oh * ow)


@register_op('im2sequence')
def im2sequence(x, *, filter_size, stride=1, padding=0):
    """ref: paddle/fluid/operators/im2sequence_op.cc (OCR feature slicing)."""
    x = jnp.asarray(x)
    n, c, h, w = x.shape
    kh, kw = _pair(filter_size)
    out = unfold(x, kernel_sizes=filter_size, strides=stride, paddings=padding)
    # (n, c*kh*kw, L) -> (n*L, c*kh*kw)
    return out.transpose(0, 2, 1).reshape(-1, c * kh * kw)


@register_op('row_conv')
def row_conv(x, w):
    """Lookahead row convolution (ref: paddle/fluid/operators/row_conv_op.cc),
    batched dense formulation: x (B, T, D), w (future_context+1, D)."""
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    ctx = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(ctx):
        shifted = jnp.pad(x, [(0, 0), (0, i), (0, 0)])[:, i:, :]
        out = out + shifted * w[i]
    return out


@register_op('bilinear_tensor_product')
def bilinear_tensor_product(x, y, weight, bias=None):
    """ref: paddle/fluid/operators/bilinear_tensor_product_op.cc.
    out[b,k] = x[b]ᵀ W[k] y[b] + bias[k]."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    w = jnp.asarray(weight)
    out = jnp.einsum('bi,kij,bj->bk', x, w, y)
    if bias is not None:
        out = out + jnp.asarray(bias)
    return out


@register_op('fsp')
def fsp(x, y):
    """Flow-of-solution-procedure matrix for distillation
    (ref: paddle/fluid/operators/fsp_op.cc)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n, c1 = x.shape[0], x.shape[1]
    c2 = y.shape[1]
    hw = x.shape[2] * x.shape[3]
    xm = x.reshape(n, c1, hw)
    ym = y.reshape(n, c2, hw)
    return jnp.einsum('nch,ndh->ncd', xm, ym) / hw


@register_op('add_position_encoding')
def add_position_encoding(x, *, alpha=1.0, beta=1.0):
    """ref: paddle/fluid/operators/add_position_encoding_op.cc."""
    x = jnp.asarray(x)
    b, t, d = x.shape
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=1)
    return alpha * x + beta * pe[None, :, :].astype(x.dtype)


@register_op('grid_sampler')
def grid_sampler(x, grid):
    """Bilinear grid sample (ref: paddle/fluid/operators/grid_sampler_op.cc).
    x: NCHW, grid: NHW2 in [-1, 1]."""
    x = jnp.asarray(x)
    grid = jnp.asarray(grid)
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)

    def sample(yy, xx):
        yi = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xi = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        batch = jnp.arange(n)[:, None, None]
        v = x[batch, :, yi, xi]  # n, gh, gw, c
        inb = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1))
        return jnp.where(inb[..., None], v, 0.0)

    wx = (gx - x0)[..., None]
    wy = (gy - y0)[..., None]
    v00 = sample(y0, x0)
    v01 = sample(y0, x0 + 1)
    v10 = sample(y0 + 1, x0)
    v11 = sample(y0 + 1, x0 + 1)
    out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
           + v10 * (1 - wx) * wy + v11 * wx * wy)
    return out.transpose(0, 3, 1, 2)


@register_op('affine_grid')
def affine_grid(theta, *, out_shape):
    """ref: paddle/fluid/operators/affine_grid_op.cc. theta: (N,2,3)."""
    theta = jnp.asarray(theta)
    n, _, h, w = out_shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing='ij')
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], -1)  # h,w,3
    return jnp.einsum('hwk,njk->nhwj', base.astype(theta.dtype), theta)


@register_op('affine_channel')
def affine_channel(x, scale, bias, *, data_layout='NCHW'):
    x = jnp.asarray(x)
    shape = (1, -1, 1, 1) if data_layout == 'NCHW' else (1, 1, 1, -1)
    return x * jnp.asarray(scale).reshape(shape) + jnp.asarray(bias).reshape(shape)


@register_op('l2_normalize')
def l2_normalize(x, *, axis=-1, epsilon=1e-12):
    x = jnp.asarray(x)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return x / jnp.maximum(norm, epsilon)


@register_op('norm', outputs=['Out', 'Norm'])
def norm(x, *, axis=-1, epsilon=1e-10):
    x = jnp.asarray(x)
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + epsilon)
    return x / n, n


# ---------------------------------------------------------------------------
# Fused / paged attention: explicit kernel dispatch.
#
# `fused_attention` and `paged_prefill_attention` have a stock pallas TPU
# kernel (flash attention) and an XLA formulation. Which one runs is a
# predicate on backend, rank, dtype and the kernel's own shape rules,
# written beside the call. Where the predicate holds the kernel runs and a
# compiler refusal is an error; where it does not, the XLA formulation runs
# because the code says so. Nothing is caught: the op bodies run at trace
# time inside the eager kernel-cache jit, so a Mosaic refusal would surface
# at compile anyway, outside any handler here. The causal grouped form of
# `paged_prefill_attention` (a model with layer classes) has the stock
# splash-attention kernel and `causal_kernel_applies`. `paged_attention`'s
# single-query read of a pool of q's own heads has no kernel and no
# predicate: one XLA formulation for every head_dim and pool dtype
# (:func:`_live_block_attention`). Its grouped reads (and the latent read)
# walk live groups (:func:`_live_group_attention`): the repo's own pallas
# kernel, ops/pallas_group_read.py, and `group_read_kernel_applies`.
#
# The flash rule was established on a TPU v5e with jax 0.9.0 (PERF.md
# section 6, PR 21): the kernel lowers at head_dim 16/64/128 whenever both
# sequence extents are multiples of its 128-row blocks.
# ---------------------------------------------------------------------------

# jax.experimental.pallas.ops.tpu.flash_attention BlockSizes.get_default:
# every forward and backward block is 128 rows, and _verify_block raises
# for a sequence that is shorter than its block or not a multiple of it
_FLASH_BLOCK = 128


def flash_kernel_applies(q, k):
    """True when `fused_attention` / `paged_prefill_attention` run the
    pallas flash kernel for (B, H, S, D) ``q``/``k`` (arrays or
    ShapeDtypeStructs): a TPU backend, rank 4, f32 or bf16, and both
    sequence extents whole multiples of the kernel's 128-row blocks."""
    return (on_tpu() and len(q.shape) == 4 and len(k.shape) == 4
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and q.shape[2] % _FLASH_BLOCK == 0
            and k.shape[2] % _FLASH_BLOCK == 0)


@register_op('fused_attention')
def fused_attention(q, k, v, bias=None, *, sm_scale=1.0, causal=False):
    """Fused multi-head attention, (B, H, S, D) layout. Where
    :func:`flash_kernel_applies` holds this lowers to the pallas
    flash-attention kernel (jax.experimental.pallas.ops.tpu.flash_attention
    — online softmax, no S×S materialization, custom vjp); everywhere else
    it is the XLA softmax(QKᵀ)V form that the compiler fuses. Measured on
    v5e (round 4, `git show b8d4f4f:PERF.md` §3): XLA wins on raw step time
    up to S=2048 (56-73 TF/s vs 13-26), so this op is NOT the default attention
    path — its value is the O(S) memory footprint for long-context configs
    where the S×S score tensor won't fit."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    if flash_kernel_applies(q, k):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        # the kernel computes (QKᵀ + ab)·sm_scale; our contract is
        # QKᵀ·sm_scale + bias, so pre-divide the bias
        ab = None if bias is None else jnp.broadcast_to(
            jnp.asarray(bias) / float(sm_scale),
            q.shape[:3] + (k.shape[2],))
        return flash_attention(q, k, v, ab=ab, causal=causal,
                               sm_scale=float(sm_scale))
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * sm_scale
    if bias is not None:
        scores = scores + jnp.asarray(bias)
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', probs, v)


# Live blocks one chunk of the single-query decode read takes from the pool:
# the work of a step is chunks × this, so the last chunk's padding (half a
# chunk on average) is read for nothing, and each chunk costs a loop
# iteration of small ops. At GPT-1's rows a chunk's K or V is
# 256 × 16 × 768 f32 = 12.6 MB.
LIVE_BLOCK_CHUNK = 256


def live_block_chunk(table_entries):
    """Blocks a chunk of :func:`paged_attention`'s read holds when the block
    tables have ``table_entries`` = S × max_blocks entries: the whole list
    where it is shorter than a chunk."""
    return min(LIVE_BLOCK_CHUNK, int(table_entries))


def live_block_list(block_tables, context_lens, block_size):
    """The LIVE blocks of a decode batch as they lie in the pool, compacted
    slot-major on the device: entry (s, j) of ``block_tables`` (S,
    max_blocks) is live iff ``j < ceil(context_lens[s] / block_size)``.

    Returns ``(block_id, slot, first_pos, n_live)``: three int32 arrays of
    S × max_blocks entries rounded up to whole chunks
    (:func:`live_block_chunk`), and the int32 count. Entry n < n_live is
    block ``block_id[n]`` of the pool, holding positions ``first_pos[n]`` …
    of slot ``slot[n]``; a slot's blocks are contiguous and in sequence
    order. Entries past ``n_live`` name the scratch block (0) at
    ``first_pos`` = the padded context, which no slot's context reaches:
    a reader's own mask (position < context) gives them zero mass.

    The list depends on the step's tables and lengths alone, so one list
    serves every layer of a program (`CacheContext.live_blocks`)."""
    tables = jnp.asarray(block_tables, jnp.int32)
    s, mb = tables.shape
    chunk = live_block_chunk(s * mb)
    at = jnp.arange(-(-s * mb // chunk) * chunk, dtype=jnp.int32)
    blocks = -(-jnp.asarray(context_lens, jnp.int32) // block_size)   # (S,)
    ends = jnp.cumsum(blocks)
    n_live = ends[-1]
    # entry n belongs to the first slot whose blocks end past n
    slot = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), s - 1)
    j = at - (ends - blocks)[slot]
    live = at < n_live
    block_id = jnp.where(live, tables[slot, jnp.clip(j, 0, mb - 1)], 0)
    first_pos = jnp.where(live, j * block_size, mb * block_size)
    return block_id, slot, first_pos, n_live


# The multi-query BLOCK read (a window model's step: B rows a slot, every row
# at the same extent) walks the live context in GROUPS of whole cache blocks,
# 128 keys a group where the table is that wide: a group's K or V is one
# (128, heads·head_dim) tile per key/value head, so that a group's scores are
# one (query rows, head_dim) x (head_dim, 128) matmul a key/value head, not
# eight of 16 keys. A chunk of the XLA walk holds this many groups (at SDAR's
# rows a chunk's K is 128 x 128 x 512 bf16 = 16.8 MB); fewer, larger chunks
# rewrite the per-slot running state less often, and the last chunk's
# padding (half a chunk on average) names the scratch block. The pallas
# kernel that replaces the walk on a TPU (ops/pallas_group_read.py) takes
# the same list and has no chunk.
LIVE_GROUP_KEYS = 128
LIVE_GROUP_CHUNK = 128


def live_group_blocks(block_size, max_blocks):
    """Cache blocks a group of the block read holds: ``LIVE_GROUP_KEYS``
    keys' worth, at least one, at most a slot's whole table."""
    return max(1, min(LIVE_GROUP_KEYS // int(block_size), int(max_blocks)))


def live_group_chunk(slots, block_size, max_blocks):
    """(groups a slot's table holds, groups a chunk of the walk holds)."""
    per_slot = -(-int(max_blocks) // live_group_blocks(block_size, max_blocks))
    return per_slot, min(LIVE_GROUP_CHUNK, int(slots) * per_slot)


def live_group_list(block_tables, context_lens, block_size):
    """The LIVE groups of a decode batch, compacted slot-major on the
    device: :func:`live_block_list` at the grain of
    :func:`live_group_blocks` blocks. Group j of slot s is live iff
    ``j·keys < context_lens[s]``, keys = blocks a group × ``block_size``.

    Returns ``(block_ids, slot, first_pos, n_live)``: (N, blocks a group),
    (N,), (N,) int32 over N = S × groups a slot rounded up to whole chunks,
    and the int32 count. A live group's trailing blocks past the slot's
    context are whatever its table holds there (the scratch block, or
    reserved blocks not yet written) and entries past ``n_live`` name the
    scratch block at ``first_pos`` = the padded context: a reader's own
    mask (position < context) gives both zero mass."""
    tables = jnp.asarray(block_tables, jnp.int32)
    s, mb = tables.shape
    m = live_group_blocks(block_size, mb)
    per_slot, chunk = live_group_chunk(s, block_size, mb)
    tables = jnp.pad(tables, ((0, 0), (0, per_slot * m - mb))).reshape(
        s, per_slot, m)
    at = jnp.arange(-(-s * per_slot // chunk) * chunk, dtype=jnp.int32)
    groups = -(-jnp.asarray(context_lens, jnp.int32) // (m * block_size))
    ends = jnp.cumsum(groups)
    n_live = ends[-1]
    slot = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), s - 1)
    j = at - (ends - groups)[slot]
    live = at < n_live
    block_ids = jnp.where(live[:, None],
                          tables[slot, jnp.clip(j, 0, per_slot - 1)], 0)
    first_pos = jnp.where(live, j * (m * block_size), mb * block_size)
    return block_ids, slot, first_pos, n_live


def live_ring_group_chunk(slots, block_size, ring, span):
    """(blocks a group holds, groups a span can touch in a slot's ring,
    groups a chunk of the walk holds) of the sliding class's read."""
    m = live_group_blocks(block_size, ring)
    per_slot = -(-int(span) // (m * int(block_size))) + 1
    return m, per_slot, min(LIVE_GROUP_CHUNK, int(slots) * per_slot)


def live_ring_group_list(ring_tables, context_lens, block_size, span):
    """:func:`live_group_list` of the SLIDING class: ``ring_tables`` (S, R)
    hold each slot's ring, position p in ring block ``(p // block_size) mod
    R``, and of a slot at context c the positions [max(0, c - span), c) are
    live. Group j (``keys`` positions, as :func:`live_group_blocks` cuts
    them, at the ring's width) of a slot is live iff it holds one of them.

    Returns ``(block_ids, slot, first_pos, n_live)`` as that function does,
    over N = S × (span / keys + 2) entries rounded up to whole chunks;
    ``first_pos`` is the group's first POSITION in the sequence, not its
    place in the ring, so that a reader masks by position: a block of a
    live group that the ring has since given to a later position, or not
    yet to this one, holds keys outside [c - span, c) by that count and
    gets zero mass. Entries past ``n_live`` name the sliding class's
    scratch block at a position no context reaches."""
    tables = jnp.asarray(ring_tables, jnp.int32)
    s, ring = tables.shape
    m, per_slot, chunk = live_ring_group_chunk(s, block_size, ring, span)
    keys = m * block_size
    at = jnp.arange(-(-s * per_slot // chunk) * chunk, dtype=jnp.int32)
    ctx = jnp.asarray(context_lens, jnp.int32)
    first = jnp.maximum(ctx - int(span), 0) // keys        # (S,) first group
    groups = (ctx - 1) // keys - first + 1
    ends = jnp.cumsum(groups)
    n_live = ends[-1]
    slot = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), s - 1)
    j = first[slot] + at - (ends - groups)[slot]           # group of the seq
    live = at < n_live
    blocks = (j[:, None] * m + jnp.arange(m, dtype=jnp.int32)[None, :]) % ring
    block_ids = jnp.where(live[:, None], tables[slot[:, None], blocks], 0)
    first_pos = jnp.where(live, j * keys, jnp.iinfo(jnp.int32).max // 2)
    return block_ids, slot, first_pos, n_live


# The blocks a read over each of the three lists above takes from the pool,
# counted on the host from a step's context lengths (an idle slot's 1: the
# scratch block): the decode engine's `decode_kv_blocks_read` a layer. A
# grouped read takes whole chunks of groups where ``whole_chunks`` (the XLA
# walk, :func:`group_walk_pads`), the live groups alone where the pallas
# kernel copies them.

def group_walk_pads(dtype):
    rows = jax.ShapeDtypeStruct((), dtype)
    return not group_read_kernel_applies(rows, rows)


def live_blocks_taken(context_lens, block_size, max_blocks):
    chunk = live_block_chunk(len(context_lens) * int(max_blocks))
    live = sum(-(-int(c) // block_size) for c in context_lens)
    return -(-live // chunk) * chunk


def live_groups_taken(context_lens, block_size, max_blocks, whole_chunks):
    per_group = live_group_blocks(block_size, max_blocks)
    live = sum(-(-int(c) // (per_group * block_size)) for c in context_lens)
    if whole_chunks:
        chunk = live_group_chunk(len(context_lens), block_size,
                                 max_blocks)[1]
        live = -(-live // chunk) * chunk
    return live * per_group


def live_ring_groups_taken(context_lens, block_size, ring, span,
                           whole_chunks):
    m, _, chunk = live_ring_group_chunk(len(context_lens), block_size, ring,
                                        span)
    keys = m * block_size
    live = sum((int(c) - 1) // keys - max(int(c) - span, 0) // keys + 1
               for c in context_lens)
    if whole_chunks:
        live = -(-live // chunk) * chunk
    return live * m


@register_op('paged_attention')
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    k_scales=None, v_scales=None, live=None, *,
                    sm_scale=1.0, block_window=False, kv_heads=None,
                    span=0):
    """Single-token decode attention over a paged KV cache (the decode half
    of the serving decode engine — docs/SERVING.md "Stateful decode";
    blueprint: Ragged Paged Attention, PAPERS.md arxiv 2604.15464).

    - ``q``: (S, H, D) — one query token per decode slot — or (S, H, K, D)
      for the MULTI-QUERY decode read speculative decoding verifies with
      (K fed tokens per slot in one step; see below).
    - ``k_pages`` / ``v_pages``: (num_blocks, block_size, W ≥ H·D) — the
      cache pool, rows of one token with all its heads, zero-padded to
      whole 128-lane tiles (serving/decode/kv_cache.py says why); H and D
      are ``q``'s. Block 0 is the scratch block (inactive slots point at
      it).
    - ``block_tables``: (S, max_blocks_per_seq) int32 — each slot's cache
      blocks in sequence order; tail entries beyond the context are
      arbitrary valid block ids (never read, or masked by
      ``context_lens``).
    - ``k_scales`` / ``v_scales``: optional (num_blocks, block_size, H)
      f32 — per-row dequant scales for int8 pools (PADDLE_TPU_KV_DTYPE=
      int8). Only what a read takes from the pool is ever cast or scaled
      to f32; bf16 pools pass no scales and simply cast. Scale-zero rows
      (unwritten, incl. the scratch block) dequantize to exact zeros.
    - ``context_lens``: (S,) int32 ≥ 1 — tokens to attend per slot,
      INCLUDING the token written at position context_len-1 this step. In
      the multi-query form this is the extent of fed-token ROW 0; row j
      attends ``context_lens + j`` keys (a causal staircase over the K
      fed positions — row j sees the prior context plus fed tokens 0..j).
    - ``live``: optional, the single-query read's
      :func:`live_block_list` of these tables and lengths (the block read's
      :func:`live_group_list`), for a caller that reads many layers through
      the same tables; made here otherwise.
    - ``block_window`` (multi-query only): the BLOCK read of a window model
      (block diffusion: the K fed rows of a slot are one block, bidirectional
      inside it). ``context_lens`` is then the extent of EVERY row, the K
      rows just written included, not the staircase; ``kv_heads`` G ≤ H is
      the heads a pool row holds (query head i reads key/value head
      i // (H/G)); the read walks the live context
      (:func:`_live_group_attention`) and gathers no table whole. int8
      pools have no block read.
    - ``kv_heads`` with a single query (q of rank 3): the GROUPED read of a
      model whose pool rows hold G ≤ H key/value heads. It is the block
      read's walk with one row a slot (:func:`_live_group_attention`: a
      group of 128 keys is one matmul a key/value head for its H/G query
      heads). ``span`` S > 0 makes it a SLIDING layer's read:
      ``block_tables`` are the slots' rings
      (:func:`live_ring_group_list`) and a key at position p counts iff
      context - S <= p < context. int8 pools have neither.

    The single-query read of a pool of q's own heads has ONE formulation, for every head_dim and pool
    dtype (:func:`_live_block_attention`): it walks the batch's live blocks
    as they lie in the pool, in chunks, with a running softmax, so its work
    follows the contexts' lengths and not the table's padded width, and no
    per-slot dense copy of the context exists. It is exact softmax
    attention in float32 over positions < context_lens, nothing dropped or
    approximated; against a whole-sequence forward it differs by the
    rounding of another order of summation (a few ulp; the tests state the
    tolerance). Positions past a slot's context get *exactly-zero* mass
    (masked after the exponential, and mostly never read), so stale values
    in a reused or scratch block can never bleed (0.0 × finite == 0.0).

    The multi-query (S, H, K, D) STAIRCASE read still gathers each slot's
    whole padded table dense (:func:`_gather_pages`) and runs matmul → mask
    → softmax → matmul: no served cell runs it yet (ROADMAP S3)."""
    q = jnp.asarray(q)
    k_pages = jnp.asarray(k_pages)
    v_pages = jnp.asarray(v_pages)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    context_lens = jnp.asarray(context_lens, jnp.int32)
    if q.ndim == 4 and block_window:
        if k_scales is not None:
            raise ValueError('paged_attention: an int8 pool has no block '
                             'read')
        if live is None:
            live = live_group_list(block_tables, context_lens,
                                   k_pages.shape[1])
        return _live_group_attention(q, k_pages, v_pages, context_lens, live,
                                     int(kv_heads or q.shape[1]), sm_scale)
    if q.ndim == 3 and kv_heads is not None:
        if k_scales is not None:
            raise ValueError('paged_attention: an int8 pool has no grouped '
                             'read')
        if live is None:
            live = live_ring_group_list(
                block_tables, context_lens, k_pages.shape[1], span) \
                if span else live_group_list(block_tables, context_lens,
                                             k_pages.shape[1])
        return _live_group_attention(
            q[:, :, None, :], k_pages, v_pages, context_lens, live,
            int(kv_heads), sm_scale, int(span))[:, :, 0]
    if span:
        raise ValueError('paged_attention: span is the grouped '
                         'single-query read\'s (kv_heads)')
    if q.ndim == 4:
        # multi-query decode (speculative verify): K fed tokens per slot,
        # row j at extent context_lens + j
        s, h, kq, d = q.shape
        k = _gather_pages(k_pages, block_tables, s, h, d, k_scales)
        v = _gather_pages(v_pages, block_tables, s, h, d, v_scales)
        t_pad = k.shape[2]
        scores = jnp.matmul(q, jnp.swapaxes(k, -1, -2))    # (S, H, K, T)
        if sm_scale != 1.0:
            scores = scores * jnp.asarray(sm_scale, scores.dtype)
        valid = jnp.arange(t_pad, dtype=jnp.int32)[None, None, None, :] \
            < (context_lens[:, None, None, None]
               + jnp.arange(kq, dtype=jnp.int32)[None, None, :, None])
        scores = jnp.where(valid, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.matmul(probs, v)                        # (S, H, K, D)
    if live is None:
        live = live_block_list(block_tables, context_lens, k_pages.shape[1])
    return _live_block_attention(q, k_pages, v_pages, context_lens, live,
                                 k_scales, v_scales, sm_scale)


def _live_block_attention(q, k_pages, v_pages, context_lens, live,
                          k_scales, v_scales, sm_scale):
    """q (S, H, D) against the pool's LIVE blocks (:func:`live_block_list`),
    a chunk of C blocks at a time, only as many chunks as hold live blocks.

    Everything stays token-major, rows of W = the pool's lanes, so nothing
    has a minor dimension of head_dim: a chunk's rows are taken as stored,
    (C, block, W), and cast to f32; scores are ``rows ⊙ q[slot]`` summed per
    head by a matmul with the constant (W, H) head-indicator; an int8 pool's
    row scales multiply the (C, block, H) scores and weights, never the
    rows. The chunk is folded into per-slot running state m (S, H), l
    (S, H), acc (S, W) with the running-softmax rescale, a slot's entries
    found by the one-hot (S, C) of the chunk's ``slot``. The sums the
    matmuls take are float32's (precision HIGHEST: the MXU's default would
    round each product and weight to bf16)."""
    f32, exact = jnp.float32, lax.Precision.HIGHEST
    s, h, d = q.shape
    bs, w = k_pages.shape[1:]
    block_id, slot, first_pos, n_live = live
    chunk = live_block_chunk(block_id.shape[0])
    neg = jnp.finfo(f32).min
    # lane x of a row belongs to head x // D; a row's padding lanes to none
    head_of = (jnp.arange(w)[:, None] // d
               == jnp.arange(h)[None, :]).astype(f32)            # (W, H)
    lanes_of = head_of.T            # (H, W): a head's value to its lanes
    q_rows = (q.astype(f32) * jnp.asarray(sm_scale, f32)).reshape(s, h * d)
    q_rows = jnp.pad(q_rows, ((0, 0), (0, w - h * d)))            # (S, W)
    offsets = jnp.arange(bs, dtype=jnp.int32)
    slots = jnp.arange(s, dtype=jnp.int32)

    def fold(i, state):
        m, l, acc = state
        ids, of, pos = (lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                        for x in (block_id, slot, first_pos))
        seen = (pos[:, None] + offsets[None, :]
                < context_lens[of][:, None])[..., None]          # (C, BS, 1)
        mine = of[None, :] == slots[:, None]                      # (S, C)
        to_slot = mine.astype(f32)
        k = jnp.take(k_pages, ids, axis=0).astype(f32)            # (C, BS, W)
        scores = jnp.matmul(
            (k * q_rows[of][:, None, :]).reshape(chunk * bs, w), head_of,
            precision=exact).reshape(chunk, bs, h)
        if k_scales is not None:
            scores = scores * jnp.take(k_scales, ids, axis=0)
        scores = jnp.where(seen, scores, neg)
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(mine[:, :, None], scores.max(1)[None], neg), axis=1))
        p = jnp.where(seen, jnp.exp(scores - m_new[of][:, None, :]), 0.0)
        rescale = jnp.exp(m - m_new)                              # (S, H)
        l = l * rescale + jnp.matmul(to_slot, p.sum(1), precision=exact)
        if v_scales is not None:
            p = p * jnp.take(v_scales, ids, axis=0)
        v = jnp.take(v_pages, ids, axis=0).astype(f32)
        weighted = jnp.matmul(p.reshape(chunk * bs, h), lanes_of,
                              precision=exact).reshape(chunk, bs, w) * v
        acc = (acc * jnp.matmul(rescale, lanes_of, precision=exact)
               + jnp.matmul(to_slot, weighted.sum(1), precision=exact))
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, -(-n_live // chunk), fold,
        (jnp.full((s, h), neg, f32), jnp.zeros((s, h), f32),
         jnp.zeros((s, w), f32)))
    out = acc[:, :h * d] / jnp.matmul(l, lanes_of[:, :h * d],
                                      precision=exact)
    return out.reshape(s, h, d).astype(q.dtype)


def _live_group_attention(q, k_pages, v_pages, context_lens, live, kv_heads,
                          sm_scale, span=0):
    """The grouped read over the pool's LIVE groups, the four callers' one
    entry (a window model's block read, a full and a sliding layer's
    one-token read, the latent read). On a TPU
    (`pallas_group_read.group_read_kernel_applies`) it is the pallas kernel
    of ops/pallas_group_read.py, which copies each live block from the pool
    into VMEM once; elsewhere the XLA walk :func:`_live_group_walk`, the
    same mathematics, because the code says so (the CPU tests)."""
    if group_read_kernel_applies(q, k_pages):
        return group_read(q, k_pages, v_pages, context_lens, live, kv_heads,
                          sm_scale, span)
    return _live_group_walk(q, k_pages, v_pages, context_lens, live,
                            kv_heads, sm_scale, span)


def _live_group_walk(q, k_pages, v_pages, context_lens, live, kv_heads,
                     sm_scale, span=0):
    """q (S, H, K, D) against the pool's LIVE groups
    (:func:`live_group_list`), a chunk of C groups at a time, only as many
    chunks as hold live groups; every row of a slot sees positions <
    ``context_lens[slot]``.

    Query head i reads key/value head i // (H/G), so a slot's H·K query
    vectors are R = (H/G)·K rows for each of the G key/value heads: a
    group's scores are one (R, D) x (D, keys) matmul a head and its weighted
    sum one (R, keys) x (keys, D), on the rows as the pool stores them
    (their dtype the operands', float32 the accumulation; probabilities
    float32 until the second matmul takes them in V's dtype). A chunk is
    folded into per-slot running state m, l (S, G, R) and acc (S, G, R, D)
    with the running-softmax rescale, a slot's groups found by the one-hot
    (S, C) of the chunk's ``slot`` as in :func:`_live_block_attention`.
    Masked positions get exactly-zero mass. ``span`` S > 0: a position p
    counts only if context - S <= p as well (``live``'s ``first_pos`` are
    then positions of the sequence, :func:`live_ring_group_list`).

    ``v_pages is k_pages``: ONE array holds keys and values (a latent pool,
    ops/llm_ops.py::mla_decode_attention: G = 1, a row is the key and its
    leading lanes the value). A chunk's rows are then taken once and serve
    both matmuls, whole: the result is D wide, and the caller keeps the
    lanes that are values."""
    f32, exact = jnp.float32, lax.Precision.HIGHEST
    s, h, kq, d = q.shape
    g = int(kv_heads)
    rep = h // g
    r = rep * kq
    bs = k_pages.shape[1]
    block_ids, slot, first_pos, n_live = live
    m_blocks = block_ids.shape[1]
    keys = m_blocks * bs
    chunk = min(LIVE_GROUP_CHUNK, block_ids.shape[0])
    neg = jnp.finfo(f32).min
    qg = q.reshape(s, g, r, d)
    scale = jnp.asarray(sm_scale, f32)
    offsets = jnp.arange(keys, dtype=jnp.int32)
    slots = jnp.arange(s, dtype=jnp.int32)
    shared = v_pages is k_pages

    def rows_of(pages, ids):
        got = jnp.take(pages, ids.reshape(-1), axis=0)[..., :g * d]
        if got.dtype != q.dtype:
            got = got.astype(q.dtype)
        return got.reshape(chunk, keys, g, d)

    def fold(i, state):
        m, l, acc = state
        ids, of, pos = (lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                        for x in (block_ids, slot, first_pos))
        at = pos[:, None] + offsets[None, :]                      # (C, T)
        seen = at < context_lens[of][:, None]
        if span:
            seen = seen & (at >= context_lens[of][:, None] - span)
        seen = seen[:, None, None, :]                             # (C,1,1,T)
        mine = of[None, :] == slots[:, None]                      # (S, C)
        to_slot = mine.astype(f32)
        queries, k = qg[of], rows_of(k_pages, ids)
        scores = jnp.einsum('cgrd,ctgd->cgrt', queries, k,
                            preferred_element_type=f32) * scale
        scores = jnp.where(seen, scores, neg)
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(mine[:, :, None, None], scores.max(-1)[None], neg),
            axis=1))
        p = jnp.where(seen, jnp.exp(scores - m_new[of][..., None]), 0.0)
        rescale = jnp.exp(m - m_new)                          # (S, G, R)
        l = l * rescale + jnp.matmul(
            to_slot, p.sum(-1).reshape(chunk, g * r),
            precision=exact).reshape(s, g, r)
        v = k if shared else rows_of(v_pages, ids)
        weighted = jnp.einsum('cgrt,ctgd->cgrd', p.astype(v.dtype), v,
                              preferred_element_type=f32)
        acc = acc * rescale[..., None] + jnp.matmul(
            to_slot, weighted.reshape(chunk, g * r * d),
            precision=exact).reshape(s, g, r, d)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, -(-n_live // chunk), fold,
        (jnp.full((s, g, r), neg, f32), jnp.zeros((s, g, r), f32),
         jnp.zeros((s, g, r, d), f32)))
    return (acc / l[..., None]).reshape(s, h, kq, d).astype(q.dtype)


def _gather_pages(pages, block_tables, s, h, d, scales=None):
    """(NB, BS, W ≥ H·D) cache pool + (S, nbs) tables → dense (S, H, nbs·BS, D)
    per-slot key/value view (the XLA stand-in for the kernel's block walk):
    the slots' blocks are taken on axis 0, whole rows of a token as they
    lie in the pool, and only the gathered copy is turned head-major.

    f32 pools pass through untouched (the bitwise-contract path). Quantized
    pools dequantize AFTER the gather — int8 payload × its per-row f32
    ``scales`` ((NB, BS, H), gathered with the identical take/reshape/
    transpose, shape (S, H, nbs·BS)), bf16 payload a plain f32 cast — so
    the dense working set is f32 but the resident pool never is."""
    nb = block_tables.shape[1]
    bs = pages.shape[1]
    g = jnp.take(pages, block_tables.reshape(-1), axis=0)
    if g.shape[-1] != h * d:            # a row padded to whole lane tiles
        g = g[..., :h * d]
    g = g.reshape(s, nb * bs, h, d).transpose(0, 2, 1, 3)
    if scales is not None:
        sc = jnp.take(jnp.asarray(scales, jnp.float32),
                      block_tables.reshape(-1), axis=0)
        sc = sc.reshape(s, nb * bs, h).transpose(0, 2, 1)
        return g.astype(jnp.float32) * sc[..., None]
    if g.dtype != jnp.float32:
        return g.astype(jnp.float32)
    return g


@register_op('paged_prefill_attention')
def paged_prefill_attention(q, k, v, k_pages, v_pages, block_tables,
                            k_scales=None, v_scales=None, *,
                            sm_scale=1.0, block_len=0, kv_heads=None,
                            span=0):
    """Prefill-phase attention for the decode engine: causal whole-prompt
    attention whose KEY EXTENT is the paged-cache view, so prefill rows are
    bitwise-identical to the decode steps (and to a whole-sequence forward
    at the engine's padded context length) that later attend to the same
    cache through `paged_attention`.

    - ``q``/``k``/``v``: (B, H, Lq, D) — the bucket-padded prompt's
      projections (the caller has ALREADY written k/v into the cache
      blocks; they are passed for the TPU kernel path, which attends the
      raw whole sequence without the gather).
    - ``k_pages``/``v_pages``/``block_tables``: the cache view, as in
      :func:`paged_attention` (tables (B, max_blocks_per_seq)).

    Row r attends keys 0..r (causal). Rows past the real prompt length are
    garbage-in-garbage-out: finite, never read, and overwritten by decode
    steps before any masked read could see them.

    ``k_scales``/``v_scales``: per-row dequant scales for int8 pools, as in
    :func:`paged_attention`. Quantized pools take the XLA gather+dequant
    path on every backend (the raw-k/v TPU kernel would attend the
    UN-quantized projections — bitwise-different from the decode steps that
    later read the quantized cache, breaking the prefill/decode parity the
    engine is built on).

    ``block_len`` B > 0 is the BLOCK mask of a window model (block
    diffusion): key j is visible to row i iff j // B <= i // B, causal
    across blocks and bidirectional inside one, and ``k``/``v`` may hold
    G ≤ H heads (query head i reads key/value head i // (H/G)). Such a
    prefill attends the raw projections it was handed, the prompt itself,
    in chunks of query rows (:func:`_block_prefill_attention`): the pool
    holds the same values (a quantized pool is refused by the engine), and
    nothing of a table is gathered.

    ``kv_heads`` G (the heads ``k``/``v`` hold, G ≤ H) is the CAUSAL
    grouped-head form of a model with layer classes: row i sees key j iff
    j <= i, and with ``span`` S > 0 iff 0 <= i - j < S as well (a sliding
    layer). It too attends the raw projections: where
    :func:`causal_kernel_applies` holds, the stock pallas splash-attention
    kernel under a causal or a local mask, a key/value head's H/G query
    heads at a time (:func:`_splash_prefill_attention`); elsewhere in
    chunks of query rows against chunks of keys with a running softmax
    (:func:`_causal_prefill_attention`). In neither does an array grow
    with the square of the rung."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    if block_len:
        return _block_prefill_attention(q, k, v, int(block_len), sm_scale)
    if kv_heads is not None:
        if k.shape[1] != int(kv_heads):
            raise ValueError(f'paged_prefill_attention: kv_heads={kv_heads} '
                             f'but k holds {k.shape[1]} heads')
        if causal_kernel_applies(q):
            return _splash_prefill_attention(q, k, v, int(span), sm_scale)
        return _causal_prefill_attention(q, k, v, int(span), sm_scale)
    if (flash_kernel_applies(q, k)
            and jnp.asarray(k_pages).dtype == jnp.float32):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        return flash_attention(q, k, v, causal=True,
                               sm_scale=float(sm_scale))
    b, h, lq, d = q.shape
    kd = _gather_pages(jnp.asarray(k_pages),
                       jnp.asarray(block_tables, jnp.int32), b, h, d,
                       k_scales)
    vd = _gather_pages(jnp.asarray(v_pages),
                       jnp.asarray(block_tables, jnp.int32), b, h, d,
                       v_scales)
    t_pad = kd.shape[2]
    scores = jnp.matmul(q, jnp.swapaxes(kd, -1, -2))
    if sm_scale != 1.0:
        scores = scores * jnp.asarray(sm_scale, scores.dtype)
    causal = jnp.arange(t_pad, dtype=jnp.int32)[None, None, None, :] \
        <= jnp.arange(lq, dtype=jnp.int32)[None, None, :, None]
    scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.matmul(probs, vd)


# query rows of a prompt attended at a time under the block mask, each chunk
# against the keys up to its own last block: a 2,048-token rung holds at
# most (heads, 512, 2048) scores, and computes 10 of the 16 chunk pairs
_BLOCK_PREFILL_QUERY_CHUNK = 512


def _block_prefill_attention(q, k, v, block_len, sm_scale):
    """q (B, H, L, D) over k, v (B, G, L, D) under the block mask (key j
    visible to row i iff j // block_len <= i // block_len), grouped heads,
    operands as stored, float32 scores and softmax."""
    f32 = jnp.float32
    b, h, length, d = q.shape
    g = k.shape[1]
    qg = q.reshape(b, g, h // g, length, d)
    chunk = min(length, _BLOCK_PREFILL_QUERY_CHUNK)
    if length % chunk or chunk % block_len:
        chunk = length
    block_of = jnp.arange(length, dtype=jnp.int32) // block_len
    out = []
    for start in range(0, length, chunk):
        stop = start + chunk
        s = jnp.einsum('bgrqd,bgkd->bgrqk', qg[:, :, :, start:stop],
                       k[:, :, :stop], preferred_element_type=f32) \
            * jnp.asarray(sm_scale, f32)
        seen = block_of[None, :stop] <= block_of[start:stop, None]
        s = jnp.where(seen, s, jnp.finfo(f32).min)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        out.append(jnp.einsum('bgrqk,bgkd->bgrqd', p, v[:, :, :stop],
                              preferred_element_type=f32).astype(q.dtype))
    return jnp.concatenate(out, 3).reshape(b, h, length, d)


# query rows and keys a step of the causal grouped prefill holds at once:
# (G, H/G · rows, keys) float32 scores, 100 MB at 48 heads (a 16,384-row
# rung would hold 51 GB of (48, L, L) scores whole)
_CAUSAL_PREFILL_QUERY_CHUNK = 512
_CAUSAL_PREFILL_KEY_CHUNK = 1024


def _causal_prefill_attention(q, k, v, span, sm_scale):
    """q (1, H, L, D) over k, v (1, G, L, D), causal (key j visible to row i
    iff j <= i) and, ``span`` S > 0, only while i - j < S; grouped heads
    (query head i reads key/value head i // (H/G)), operands as stored,
    float32 scores and softmax.

    Query rows go a chunk at a time (`lax.map`), each against the key chunks
    that hold a key it may see, [max(0, start - S + 1), stop), folded with
    the running-softmax rescale: the work is the mask's, to a chunk, and
    the largest array is one chunk pair's scores. A rung shorter than a
    chunk is one pair."""
    f32 = jnp.float32
    _, h, length, d = q.shape
    g = k.shape[1]
    r = h // g
    cq = min(length, _CAUSAL_PREFILL_QUERY_CHUNK)
    ck = min(length, _CAUSAL_PREFILL_KEY_CHUNK)
    if length % cq or length % ck:
        cq = ck = length
    qg = q[0].reshape(g, r, length, d)
    kg, vg = k[0], v[0]                                    # (G, L, D)
    scale = jnp.asarray(sm_scale, f32)
    neg = jnp.finfo(f32).min
    rows = jnp.arange(cq, dtype=jnp.int32)
    cols = jnp.arange(ck, dtype=jnp.int32)

    def one(i):
        start = i * cq
        qi = lax.dynamic_slice_in_dim(qg, start, cq, 2).reshape(g, r * cq, d)
        at_q = jnp.tile(start + rows, r)                   # (R·cq,) positions

        def fold(j, state):
            m, l, acc = state
            kj = lax.dynamic_slice_in_dim(kg, j * ck, ck, 1)
            vj = lax.dynamic_slice_in_dim(vg, j * ck, ck, 1)
            at_k = j * ck + cols
            seen = at_k[None, :] <= at_q[:, None]
            if span:
                seen = seen & (at_q[:, None] - at_k[None, :] < span)
            s = jnp.einsum('gqd,gkd->gqk', qi, kj,
                           preferred_element_type=f32) * scale
            s = jnp.where(seen[None], s, neg)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(seen[None], jnp.exp(s - m_new[..., None]), 0.0)
            rescale = jnp.exp(m - m_new)
            l = l * rescale + p.sum(-1)
            acc = acc * rescale[..., None] + jnp.einsum(
                'gqk,gkd->gqd', p.astype(vj.dtype), vj,
                preferred_element_type=f32)
            return m_new, l, acc

        lo = jnp.maximum(start - span + 1, 0) // ck if span else 0
        hi = (start + cq - 1) // ck + 1
        m, l, acc = lax.fori_loop(
            lo, hi, fold,
            (jnp.full((g, r * cq), neg, f32), jnp.zeros((g, r * cq), f32),
             jnp.zeros((g, r * cq, d), f32)))
        return (acc / l[..., None]).astype(q.dtype).reshape(g, r, cq, d)

    out = lax.map(one, jnp.arange(length // cq, dtype=jnp.int32))
    # (chunks, G, R, cq, D) -> (1, H, L, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(1, h, length, d)


# rows and keys a block of the splash kernel holds (its scores stay in VMEM:
# 512 x 512 float32 a query head)
_SPLASH_BLOCK = 512


def causal_kernel_applies(q):
    """True when the causal grouped prefill runs the pallas splash-attention
    kernel for (1, H, L, D) ``q`` (an array or a ShapeDtypeStruct): a TPU
    backend, f32 or bf16, and a rung of whole kernel blocks. The repo's one
    convention ("explicit kernel dispatch", above): where it holds the
    kernel runs and a Mosaic refusal is an error; elsewhere the XLA
    formulation runs because the code says so (the CPU tests, a rung
    shorter than a block)."""
    return (on_tpu() and len(q.shape) == 4
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and q.shape[2] % _SPLASH_BLOCK == 0)


@functools.lru_cache(maxsize=None)
def _splash_kernel(length, span, heads, interpret=False):
    """The splash-attention MQA kernel for ``heads`` query heads over ONE
    key/value head, causal over ``length`` positions and, ``span`` S > 0,
    local to the last S: row i sees key j iff 0 <= i - j < S. Its mask is
    worked out here once a (rung, span) from the block structure alone (no
    (L, L) array exists); forward only."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    shape = (length, length)
    one = masks.LocalMask(shape, (span - 1, 0), 0) if span \
        else masks.CausalMask(shape)
    block = min(_SPLASH_BLOCK, length)
    return kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([one] * heads),
        block_sizes=kernel.BlockSizes(block_q=block, block_kv=block,
                                      block_kv_compute=block),
        interpret=interpret)


def _splash_prefill_attention(q, k, v, span, sm_scale, interpret=False):
    """:func:`_causal_prefill_attention`'s mathematics through the stock
    pallas splash-attention kernel: q (1, H, L, D) over k, v (1, G, L, D),
    each key/value head with its H/G query heads as one multi-query call
    (`jax.vmap` over G), operands as stored, float32 scores, softmax and
    accumulation inside the kernel, whose blocks of scores never reach HBM.
    The kernel takes no scale: q is scaled first, in float32."""
    _, h, length, d = q.shape
    g = k.shape[1]
    kernel = _splash_kernel(length, span, h // g, interpret)
    qg = (q[0].astype(jnp.float32) * jnp.asarray(sm_scale, jnp.float32)
          ).astype(q.dtype).reshape(g, h // g, length, d)
    out = jax.vmap(kernel)(qg, k[0], v[0])              # (G, H/G, L, D)
    return out.reshape(1, h, length, d).astype(q.dtype)
