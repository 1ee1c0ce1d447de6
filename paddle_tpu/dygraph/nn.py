"""dygraph.nn layers (ref: python/paddle/fluid/dygraph/nn.py: Conv2D, Conv3D,
Pool2D, Linear, BatchNorm, Embedding, GRUUnit, LayerNorm, NCE, PRelu,
BilinearTensorProduct, Conv2DTranspose, Conv3DTranspose, GroupNorm,
SpectralNorm, TreeConv)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.dtypes import convert_dtype
from ..initializer import ConstantInitializer, NormalInitializer, XavierInitializer
from .layers import Layer
from .tape import Tensor, dispatch_op


class Conv2D(Layer):
    def __init__(self, num_channels, num_filters, filter_size, stride=1,
                 padding=0, dilation=1, groups=1, param_attr=None,
                 bias_attr=None, use_cudnn=True, act=None, dtype='float32',
                 data_format='NCHW'):
        super().__init__()
        fs = filter_size if isinstance(filter_size, (list, tuple)) \
            else (filter_size, filter_size)
        std = math.sqrt(2.0 / (fs[0] * fs[1] * num_channels))
        # NHWC keeps HWIO weights so the conv lowers with no layout
        # transposes (NHWC end-to-end was ~6% faster on v5e in round 4)
        wshape = ([num_filters, num_channels // groups, fs[0], fs[1]]
                  if data_format == 'NCHW'
                  else [fs[0], fs[1], num_channels // groups, num_filters])
        self.weight = self.create_parameter(
            wshape, param_attr, dtype,
            default_initializer=NormalInitializer(0.0, std))
        self.bias = self.create_parameter([num_filters], bias_attr, dtype,
                                          is_bias=True)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups, data_format=data_format)
        self._bias_axis = 1 if data_format == 'NCHW' else -1
        self._act = act

    def forward(self, x):
        out = dispatch_op('conv2d', {'x': x, 'weight': self.weight},
                          self._attrs)
        if self.bias is not None:
            out = dispatch_op('elementwise_add',
                              {'x': out, 'y': self.bias},
                              {'axis': self._bias_axis})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class Conv3D(Layer):
    def __init__(self, num_channels, num_filters, filter_size, stride=1,
                 padding=0, dilation=1, groups=1, param_attr=None,
                 bias_attr=None, act=None, dtype='float32'):
        super().__init__()
        fs = filter_size if isinstance(filter_size, (list, tuple)) \
            else (filter_size,) * 3
        self.weight = self.create_parameter(
            [num_filters, num_channels // groups, *fs], param_attr, dtype)
        self.bias = self.create_parameter([num_filters], bias_attr, dtype,
                                          is_bias=True)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups)
        self._act = act

    def forward(self, x):
        out = dispatch_op('conv3d', {'x': x, 'weight': self.weight}, self._attrs)
        if self.bias is not None:
            out = dispatch_op('elementwise_add', {'x': out, 'y': self.bias},
                              {'axis': 1})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class Conv2DTranspose(Layer):
    def __init__(self, num_channels, num_filters, filter_size, output_size=None,
                 padding=0, stride=1, dilation=1, groups=1, param_attr=None,
                 bias_attr=None, use_cudnn=True, act=None, dtype='float32'):
        super().__init__()
        fs = filter_size if isinstance(filter_size, (list, tuple)) \
            else (filter_size, filter_size)
        self.weight = self.create_parameter(
            [num_channels, num_filters // groups, fs[0], fs[1]], param_attr,
            dtype)
        self.bias = self.create_parameter([num_filters], bias_attr, dtype,
                                          is_bias=True)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups)
        self._act = act

    def forward(self, x):
        out = dispatch_op('conv2d_transpose',
                          {'x': x, 'weight': self.weight}, self._attrs)
        if self.bias is not None:
            out = dispatch_op('elementwise_add', {'x': out, 'y': self.bias},
                              {'axis': 1})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class Conv3DTranspose(Layer):
    def __init__(self, num_channels, num_filters, filter_size, padding=0,
                 stride=1, dilation=1, groups=1, param_attr=None,
                 bias_attr=None, act=None, dtype='float32'):
        super().__init__()
        fs = filter_size if isinstance(filter_size, (list, tuple)) \
            else (filter_size,) * 3
        self.weight = self.create_parameter(
            [num_channels, num_filters // groups, *fs], param_attr, dtype)
        self.bias = self.create_parameter([num_filters], bias_attr, dtype,
                                          is_bias=True)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups)
        self._act = act

    def forward(self, x):
        out = dispatch_op('conv3d_transpose', {'x': x, 'weight': self.weight},
                          self._attrs)
        if self.bias is not None:
            out = dispatch_op('elementwise_add', {'x': out, 'y': self.bias},
                              {'axis': 1})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class Pool2D(Layer):
    def __init__(self, pool_size=-1, pool_type='max', pool_stride=1,
                 pool_padding=0, global_pooling=False, use_cudnn=True,
                 ceil_mode=False, exclusive=True, data_format='NCHW'):
        super().__init__()
        self._attrs = dict(pool_size=pool_size, pool_type=pool_type,
                           pool_stride=pool_stride, pool_padding=pool_padding,
                           global_pooling=global_pooling, ceil_mode=ceil_mode,
                           exclusive=exclusive, data_format=data_format)

    def forward(self, x):
        return dispatch_op('pool2d', {'x': x}, self._attrs)


class Linear(Layer):
    def __init__(self, input_dim, output_dim, param_attr=None, bias_attr=None,
                 act=None, dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter([input_dim, output_dim],
                                            param_attr, dtype)
        self.bias = self.create_parameter([output_dim], bias_attr, dtype,
                                          is_bias=True)
        self._act = act

    def forward(self, x):
        out = dispatch_op('matmul', {'x': x, 'y': self.weight}, {})
        if self.bias is not None:
            out = dispatch_op('elementwise_add', {'x': out, 'y': self.bias},
                              {'axis': -1})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class BatchNorm(Layer):
    def __init__(self, num_channels, act=None, is_test=False, momentum=0.9,
                 epsilon=1e-5, param_attr=None, bias_attr=None,
                 dtype='float32', data_layout='NCHW', in_place=False,
                 moving_mean_name=None, moving_variance_name=None,
                 do_model_average_for_mean_and_var=True,
                 use_global_stats=False, trainable_statistics=False,
                 sync_stats=False):
        super().__init__()
        self.weight = self.create_parameter(
            [num_channels], param_attr, dtype,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter([num_channels], bias_attr, dtype,
                                          is_bias=True)
        self._mean = self.register_buffer(
            '_mean_buf', self.create_buffer([num_channels], dtype, 0.0))
        self._variance = self.register_buffer(
            '_variance_buf', self.create_buffer([num_channels], dtype, 1.0))
        self._attrs = dict(momentum=momentum, epsilon=epsilon,
                           data_layout=data_layout,
                           use_global_stats=use_global_stats,
                           sync_stats=sync_stats)

    def forward(self, x):
        y, new_mean, new_var = dispatch_op(
            'batch_norm',
            {'x': x, 'scale': self.weight, 'bias': self.bias,
             'mean': self._mean, 'variance': self._variance},
            dict(self._attrs, is_test=not self.training))
        if self.training:
            self._mean.value = new_mean.value
            self._variance.value = new_var.value
        return y


class Embedding(Layer):
    def __init__(self, size, is_sparse=False, is_distributed=False,
                 padding_idx=None, param_attr=None, dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter(
            list(size), param_attr, dtype,
            default_initializer=XavierInitializer())
        pad = -1 if padding_idx is None else (
            padding_idx if padding_idx >= 0 else size[0] + padding_idx)
        # is_sparse is LIVE (was: accepted-and-dropped): the tape emits
        # rows-only COO gradients for this table (docs/SPARSE.md)
        self._attrs = dict(padding_idx=pad, is_sparse=is_sparse,
                           is_distributed=is_distributed)

    def forward(self, ids):
        return dispatch_op('lookup_table', {'w': self.weight, 'ids': ids},
                           self._attrs)


class LayerNorm(Layer):
    def __init__(self, normalized_shape, scale=True, shift=True,
                 epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
                 dtype='float32'):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        n = math.prod(normalized_shape)
        self.weight = self.create_parameter(
            [n], param_attr, dtype,
            default_initializer=ConstantInitializer(1.0)) if scale else None
        self.bias = self.create_parameter([n], bias_attr, dtype,
                                          is_bias=True) if shift else None
        self._epsilon = epsilon
        self._ndims = len(normalized_shape)
        self._act = act

    def forward(self, x):
        begin = x.ndim - self._ndims
        out = dispatch_op('layer_norm',
                          {'x': x, 'scale': self.weight, 'bias': self.bias},
                          {'begin_norm_axis': begin, 'epsilon': self._epsilon})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class GroupNorm(Layer):
    def __init__(self, channels, groups, epsilon=1e-5, param_attr=None,
                 bias_attr=None, act=None, data_layout='NCHW', dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter(
            [channels], param_attr, dtype,
            default_initializer=ConstantInitializer(1.0))
        self.bias = self.create_parameter([channels], bias_attr, dtype,
                                          is_bias=True)
        self._attrs = dict(groups=groups, epsilon=epsilon,
                           data_layout=data_layout)
        self._act = act

    def forward(self, x):
        out = dispatch_op('group_norm',
                          {'x': x, 'scale': self.weight, 'bias': self.bias},
                          self._attrs)
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter(list(weight_shape), None, dtype)
        self._attrs = dict(dim=dim, power_iters=power_iters, eps=eps)

    def forward(self, weight=None):
        w = weight if weight is not None else self.weight
        return dispatch_op('spectral_norm', {'w': w}, self._attrs)


class PRelu(Layer):
    def __init__(self, mode, channel=None, input_shape=None, param_attr=None,
                 dtype='float32'):
        super().__init__()
        if mode == 'all':
            shape = [1]
        elif mode == 'channel':
            shape = [channel]
        else:
            shape = [math.prod(input_shape[1:])]
        self.weight = self.create_parameter(
            shape, param_attr, dtype,
            default_initializer=ConstantInitializer(0.25))
        self._mode = mode

    def forward(self, x):
        return dispatch_op('prelu', {'x': x, 'alpha': self.weight},
                           {'mode': self._mode})


class BilinearTensorProduct(Layer):
    def __init__(self, input1_dim, input2_dim, output_dim, name=None,
                 act=None, param_attr=None, bias_attr=None, dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter(
            [output_dim, input1_dim, input2_dim], param_attr, dtype)
        self.bias = self.create_parameter([output_dim], bias_attr, dtype,
                                          is_bias=True)
        self._act = act

    def forward(self, x, y):
        out = dispatch_op('bilinear_tensor_product',
                          {'x': x, 'y': y, 'weight': self.weight,
                           'bias': self.bias}, {})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class GRUUnit(Layer):
    def __init__(self, size, param_attr=None, bias_attr=None,
                 activation='tanh', gate_activation='sigmoid',
                 origin_mode=False, dtype='float32'):
        super().__init__()
        d = size // 3
        self.weight = self.create_parameter([d, d * 3], param_attr, dtype)
        self.bias = self.create_parameter([1, d * 3], bias_attr, dtype,
                                          is_bias=True)
        self._d = d
        self._origin_mode = origin_mode
        self._act = activation
        self._gate_act = gate_activation

    def forward(self, inputs, hidden):
        h, rh, gate = dispatch_op(
            'gru_unit', {'x': inputs, 'hidden': hidden,
                         'weight': self.weight, 'bias': self.bias},
            {'origin_mode': self._origin_mode})
        return h, rh, gate


class NCE(Layer):
    def __init__(self, num_total_classes, dim, sample_weight=None,
                 param_attr=None, bias_attr=None, num_neg_samples=10,
                 sampler='uniform', custom_dist=None, seed=0,
                 is_sparse=False, dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter([num_total_classes, dim],
                                            param_attr, dtype)
        self.bias = self.create_parameter([num_total_classes], bias_attr,
                                          dtype, is_bias=True)
        self._attrs = dict(num_total_classes=num_total_classes,
                           num_neg_samples=num_neg_samples)

    def forward(self, input, label, sample_weight=None):
        return dispatch_op('nce', {'x': input, 'label': label,
                                   'weight': self.weight, 'bias': self.bias},
                           self._attrs)


class TreeConv(Layer):
    def __init__(self, feature_size, output_size, num_filters=1,
                 max_depth=8, act='tanh', param_attr=None, bias_attr=None,
                 name=None, dtype='float32'):
        super().__init__()
        self.weight = self.create_parameter(
            [feature_size, 3, output_size, num_filters], param_attr, dtype)
        self.bias = self.create_parameter([num_filters, output_size],
                                          bias_attr, dtype, is_bias=True)
        self._max_depth = max_depth
        self._act = act

    def forward(self, nodes_vector, edge_set):
        out = dispatch_op('tree_conv',
                          {'nodes': nodes_vector, 'edges': edge_set,
                           'weight': self.weight},
                          {'max_depth': self._max_depth})
        if self._act:
            out = dispatch_op(self._act, {'x': out}, {})
        return out


class Dropout(Layer):
    def __init__(self, p=0.5, seed=None,
                 dropout_implementation='downgrade_in_infer',
                 is_test=False):
        super().__init__()
        self._p = p
        self._impl = dropout_implementation

    def forward(self, x):
        return dispatch_op('dropout', {'x': x},
                           {'dropout_prob': self._p,
                            'is_test': not self.training,
                            'dropout_implementation': self._impl})
