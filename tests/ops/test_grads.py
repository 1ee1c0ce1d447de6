"""OpTest-style gradient checks over the op registry (SURVEY §4; ref
python/paddle/fluid/tests/unittests/op_test.py:1261 check_grad).

Every registered op must be classified: either a GRAD_SPECS entry (finite
difference check via jax.test_util.check_grads on small shapes) or a
NONDIFF entry with a reason string. A completeness guard fails when a new
op lands unclassified."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.test_util import check_grads

import paddle_tpu  # noqa: F401  (registers all ops)
from paddle_tpu.ops.registry import _REGISTRY, get_op

R = np.random.RandomState


def f32(a):
    return np.asarray(a, np.float32)


def away(rng, shape, lo=0.2, hi=1.0):
    """Floats bounded away from 0 (kink-free for abs/relu/sign-like ops)."""
    return f32(rng.uniform(lo, hi, shape) * np.where(
        rng.rand(*shape) < 0.5, -1.0, 1.0))


def pos(rng, shape, lo=0.3, hi=2.0):
    return f32(rng.uniform(lo, hi, shape))


def probs(rng, shape):
    x = rng.uniform(0.1, 1.0, shape)
    return f32(x / x.sum(-1, keepdims=True))


def S(args, diff=(0,), attrs=None, tol=2e-2, eps=None):
    return {'args': args, 'diff': diff, 'attrs': attrs or {}, 'tol': tol,
            'eps': eps}


def _std(shape):
    return lambda rng: [f32(rng.standard_normal(shape))]


# ---------------------------------------------------------------------------
# differentiable ops: name → spec(args builder, diff arg indices, attrs)
# ---------------------------------------------------------------------------
GRAD_SPECS = {
    # --- contrib text-matching ops ---
    'match_matrix_tensor': S(
        lambda r: [f32(r.standard_normal((2, 3, 4))),
                   f32(r.standard_normal((2, 5, 4))),
                   f32(r.standard_normal((4, 2, 4)))],
        diff=(0, 1, 2), attrs={'channel_num': 2}),
    'var_conv_2d': S(
        lambda r: [f32(r.standard_normal((2, 2, 5, 5))),
                   f32(r.standard_normal((3, 2, 3, 3)))],
        diff=(0, 1), attrs={'stride': 1}),
    'sequence_topk_avg_pooling': S(
        lambda r: [f32(0.1 * np.arange(48).reshape(2, 2, 3, 4) +
                       r.uniform(0, 0.03, (2, 2, 3, 4)))],
        attrs={'topks': [1, 2], 'channel_num': 2}),
    'fused_embedding_seq_pool': S(
        lambda r: [np.array([[1, 2, 0], [3, 4, 5]], np.int64),
                   f32(r.standard_normal((7, 4)))],
        diff=(1,), attrs={'combiner': 'mean'}),
    'search_pyramid_hash': S(
        lambda r: [np.array([[3, 4, 5, 6], [8, 9, 1, 2]], np.int64),
                   f32(r.standard_normal((64, 8)))],
        diff=(1,),
        attrs={'num_emb': 8, 'space_len': 64, 'pyramid_layer': 3,
               'rand_len': 8, 'drop_out_percent': 0.0,
               'is_training': False, 'seed': 1}),
    # --- unary elementwise ---
    'abs': S(lambda r: [away(r, (3, 4))]),
    'acos': S(lambda r: [f32(r.uniform(-0.8, 0.8, (3, 4)))]),
    'asin': S(lambda r: [f32(r.uniform(-0.8, 0.8, (3, 4)))]),
    'atan': S(_std((3, 4))),
    'brelu': S(lambda r: [pos(r, (3, 4), 1.0, 5.0)]),
    'cos': S(_std((3, 4))),
    'cumsum': S(_std((3, 4)), attrs={'axis': 1}),
    'cosh': S(_std((3, 4))),
    'elu': S(lambda r: [away(r, (3, 4))]),
    'erf': S(_std((3, 4))),
    'exp': S(_std((3, 4))),
    'gelu': S(_std((3, 4))),
    'hard_shrink': S(lambda r: [away(r, (3, 4), 0.7, 1.5)]),
    'hard_sigmoid': S(lambda r: [f32(r.uniform(-1.5, 1.5, (3, 4)))]),
    'hard_swish': S(lambda r: [f32(r.uniform(-2.0, 2.0, (3, 4)))]),
    'leaky_relu': S(lambda r: [away(r, (3, 4))]),
    'log': S(lambda r: [pos(r, (3, 4))]),
    'log_softmax': S(_std((3, 4))),
    'logsigmoid': S(_std((3, 4))),
    'logsumexp': S(_std((3, 4))),
    'mean': S(_std((3, 4))),
    'pow': S(lambda r: [pos(r, (3, 4))], attrs={'factor': 1.7}),
    'reciprocal': S(lambda r: [pos(r, (3, 4), 0.5, 2.0)]),
    'relu': S(lambda r: [away(r, (3, 4))]),
    # fused (add, act) pair from the IR pass pipeline: x + y kept away
    # from relu's kink by construction
    'fused_elemwise_add_activation': S(
        lambda r: [away(r, (3, 4), 1.0, 2.0),
                   f32(r.uniform(-0.3, 0.3, (3, 4)))],
        diff=(0, 1), attrs={'functor': 'relu'}),
    'relu6': S(lambda r: [pos(r, (3, 4), 0.5, 5.0)]),
    'rsqrt': S(lambda r: [pos(r, (3, 4))]),
    'scale': S(_std((3, 4)), attrs={'scale': 2.5, 'bias': 0.3}),
    'selu': S(lambda r: [away(r, (3, 4))]),
    'sigmoid': S(_std((3, 4))),
    'sin': S(_std((3, 4))),
    'sinh': S(_std((3, 4))),
    'soft_relu': S(_std((3, 4))),
    'softmax': S(_std((3, 4))),
    'softplus': S(_std((3, 4))),
    'softshrink': S(lambda r: [away(r, (3, 4), 0.8, 1.5)]),
    'softsign': S(_std((3, 4))),
    'sqrt': S(lambda r: [pos(r, (3, 4))]),
    'square': S(_std((3, 4))),
    'stanh': S(_std((3, 4))),
    'swish': S(_std((3, 4))),
    'tanh': S(_std((3, 4))),
    'tanh_shrink': S(_std((3, 4))),
    'thresholded_relu': S(lambda r: [pos(r, (3, 4), 1.3, 2.0)]),
    'increment': S(_std((1,))),
    'clip': S(lambda r: [f32(r.uniform(-0.8, 0.8, (3, 4)))],
              attrs={'min': -1.0, 'max': 1.0}),
    'clip_by_norm': S(_std((3, 4)), attrs={'max_norm': 1.0}),
    'l2_normalize': S(lambda r: [away(r, (3, 4), 0.5, 1.5)]),
    'norm': S(lambda r: [away(r, (3, 4), 0.5, 1.5)]),
    'add_position_encoding': S(_std((2, 3, 8))),
    'label_smooth': S(lambda r: [probs(r, (3, 4)), None],
                      attrs={'epsilon': 0.1}),
    # --- binary / broadcast ---
    'elementwise_add': S(lambda r: [f32(r.standard_normal((3, 4))),
                                    f32(r.standard_normal((3, 4)))],
                         diff=(0, 1)),
    'elementwise_sub': S(lambda r: [f32(r.standard_normal((3, 4))),
                                    f32(r.standard_normal((3, 4)))],
                         diff=(0, 1)),
    'elementwise_mul': S(lambda r: [f32(r.standard_normal((3, 4))),
                                    f32(r.standard_normal((3, 4)))],
                         diff=(0, 1)),
    'elementwise_div': S(lambda r: [f32(r.standard_normal((3, 4))),
                                    pos(r, (3, 4), 0.5, 2.0)], diff=(0, 1)),
    'elementwise_max': S(lambda r: [f32(r.uniform(1.0, 2.0, (3, 4))),
                                    f32(r.uniform(-2.0, -1.0, (3, 4)))],
                         diff=(0, 1)),
    'elementwise_min': S(lambda r: [f32(r.uniform(1.0, 2.0, (3, 4))),
                                    f32(r.uniform(-2.0, -1.0, (3, 4)))],
                         diff=(0, 1)),
    'elementwise_pow': S(lambda r: [pos(r, (3, 4)), pos(r, (3, 4))],
                         diff=(0, 1)),
    'elementwise_mod': S(lambda r: [pos(r, (3, 4), 5.0, 9.0),
                                    pos(r, (3, 4), 1.8, 2.2)], diff=(0,)),
    'matmul': S(lambda r: [f32(r.standard_normal((3, 4))),
                           f32(r.standard_normal((4, 5)))], diff=(0, 1)),
    'mul': S(lambda r: [f32(r.standard_normal((3, 4))),
                        f32(r.standard_normal((4, 5)))], diff=(0, 1)),
    'dot': S(lambda r: [f32(r.standard_normal((3, 4))),
                        f32(r.standard_normal((3, 4)))], diff=(0, 1)),
    'kron': S(lambda r: [f32(r.standard_normal((2, 3))),
                         f32(r.standard_normal((3, 2)))], diff=(0, 1)),
    'fsp': S(lambda r: [f32(r.standard_normal((1, 2, 4, 4))),
                        f32(r.standard_normal((1, 3, 4, 4)))], diff=(0, 1)),
    'cos_sim': S(lambda r: [away(r, (3, 4), 0.5, 1.5),
                            away(r, (3, 4), 0.5, 1.5)], diff=(0, 1)),
    'bilinear_tensor_product': S(
        lambda r: [f32(r.standard_normal((2, 3))),
                   f32(r.standard_normal((2, 4))),
                   f32(r.standard_normal((5, 3, 4)) * 0.3), None],
        diff=(0, 1, 2)),
    'prelu': S(lambda r: [away(r, (3, 4)), f32([0.25])], diff=(0, 1)),
    'fused_attention': S(
        lambda r: [f32(r.standard_normal((1, 2, 4, 8)) * 0.3),
                   f32(r.standard_normal((1, 2, 4, 8)) * 0.3),
                   f32(r.standard_normal((1, 2, 4, 8)) * 0.3), None],
        diff=(0, 1, 2), attrs={'sm_scale': 0.35}),
    # --- reductions ---
    'reduce_sum': S(_std((3, 4))),
    'reduce_mean': S(_std((3, 4))),
    'reduce_max': S(lambda r: [f32(np.arange(12).reshape(3, 4)
                                   + r.uniform(0, 0.3, (3, 4)))]),
    'reduce_min': S(lambda r: [f32(np.arange(12).reshape(3, 4)
                                   + r.uniform(0, 0.3, (3, 4)))]),
    'reduce_prod': S(lambda r: [pos(r, (3, 4), 0.5, 1.5)]),
    'sum': S(lambda r: [[f32(r.standard_normal((3, 4))),
                         f32(r.standard_normal((3, 4)))]], diff=()),
    # --- losses ---
    'cross_entropy': S(lambda r: [probs(r, (3, 5)),
                                  r.randint(0, 5, (3, 1)).astype(np.int64)]),
    'softmax_with_cross_entropy': S(
        lambda r: [f32(r.standard_normal((3, 5))),
                   r.randint(0, 5, (3, 1)).astype(np.int64)]),
    'sigmoid_cross_entropy_with_logits': S(
        lambda r: [f32(r.standard_normal((3, 4))),
                   f32(r.randint(0, 2, (3, 4)))]),
    'sigmoid_focal_loss': S(
        lambda r: [f32(r.standard_normal((4, 3))),
                   r.randint(0, 4, (4, 1)).astype(np.int64),
                   np.asarray([2], np.int32)],
        attrs={'gamma': 2.0, 'alpha': 0.25}),
    'square_error_cost': S(lambda r: [f32(r.standard_normal((3, 4))),
                                      f32(r.standard_normal((3, 4)))],
                           diff=(0, 1)),
    'smooth_l1_loss': S(lambda r: [f32(r.standard_normal((3, 4))),
                                   f32(r.standard_normal((3, 4)) + 3.0),
                                   None, None], diff=(0, 1)),
    'huber_loss': S(lambda r: [f32(r.standard_normal((3, 1))),
                               f32(r.standard_normal((3, 1)) + 3.0)],
                    diff=(0, 1)),
    'kldiv_loss': S(lambda r: [np.log(probs(r, (3, 4))),
                               probs(r, (3, 4))], attrs={'reduction': 'mean'}),
    'log_loss': S(lambda r: [f32(r.uniform(0.15, 0.85, (3, 1))),
                             f32(r.randint(0, 2, (3, 1)))]),
    'bpr_loss': S(lambda r: [f32(r.standard_normal((3, 4))),
                             r.randint(0, 4, (3, 1)).astype(np.int64)]),
    'rank_loss': S(lambda r: [f32(r.randint(0, 2, (3, 1))),
                              f32(r.standard_normal((3, 1))),
                              f32(r.standard_normal((3, 1)))], diff=(1, 2)),
    'margin_rank_loss': S(lambda r: [f32(np.where(r.rand(3, 1) < .5, -1, 1)),
                                     f32(r.standard_normal((3, 1)) + 2),
                                     f32(r.standard_normal((3, 1)) - 2)],
                          diff=(1, 2)),
    'dice_loss': S(lambda r: [probs(r, (4, 3)),
                              r.randint(0, 3, (4, 1)).astype(np.int64)]),
    'teacher_student_sigmoid_loss': S(
        lambda r: [f32(r.standard_normal((4, 1))),
                   f32(r.uniform(0.1, 0.9, (4, 1)))]),
    'center_loss': S(
        lambda r: [f32(r.standard_normal((4, 6))),
                   r.randint(0, 5, (4, 1)).astype(np.int64),
                   f32(r.standard_normal((5, 6))), f32([0.5])],
        attrs={'cluster_num': 5, 'need_update': False}),
    'hsigmoid': S(lambda r: [f32(r.standard_normal((3, 4))),
                             r.randint(0, 6, (3, 1)).astype(np.int64),
                             f32(r.standard_normal((5, 4)) * 0.3),
                             f32(r.standard_normal((5,)) * 0.1)],
                  diff=(0, 2, 3), attrs={'num_classes': 6}),
    'warpctc': S(lambda r: [f32(r.standard_normal((6, 2, 5))),
                            r.randint(1, 5, (2, 3)).astype(np.int64),
                            np.asarray([6, 5], np.int64),
                            np.asarray([3, 2], np.int64)],
                 attrs={'blank': 0}, tol=4e-2),
    'linear_chain_crf': S(
        lambda r: [f32(r.standard_normal((2, 5, 4))),
                   f32(r.standard_normal((6, 4)) * 0.3),
                   r.randint(0, 4, (2, 5)).astype(np.int64),
                   np.asarray([5, 3], np.int64)],
        diff=(0, 1), tol=4e-2),
    # --- nn ---
    'conv2d': S(lambda r: [f32(r.standard_normal((1, 2, 5, 5))),
                           f32(r.standard_normal((3, 2, 3, 3)) * 0.3)],
                diff=(0, 1)),
    'conv2d_stem_s2d': S(lambda r: [
        f32(r.standard_normal((1, 15, 15, 3))),
        f32(r.standard_normal((7, 7, 3, 4)) * 0.2)], diff=(0, 1)),
    'fused_conv1x1_bn_act': S(lambda r: [
        f32(r.standard_normal((1, 4, 4, 6))),
        f32(r.standard_normal((1, 1, 6, 5)) * 0.3),
        f32(r.random(5) + 0.5), f32(r.standard_normal(5) * 0.1)],
        diff=(0, 1, 2, 3)),
    'conv2d_transpose': S(lambda r: [f32(r.standard_normal((1, 2, 4, 4))),
                                     f32(r.standard_normal((2, 3, 3, 3))
                                         * 0.3)], diff=(0, 1)),
    'conv3d': S(lambda r: [f32(r.standard_normal((1, 1, 4, 4, 4))),
                           f32(r.standard_normal((2, 1, 3, 3, 3)) * 0.3)],
                diff=(0, 1)),
    'conv3d_transpose': S(lambda r: [f32(r.standard_normal((1, 2, 3, 3, 3))),
                                     f32(r.standard_normal((2, 2, 3, 3, 3))
                                         * 0.3)], diff=(0, 1)),
    'deformable_conv': S(
        lambda r: [f32(r.standard_normal((1, 2, 5, 5))),
                   f32(r.standard_normal((1, 18, 3, 3)) * 0.1),
                   f32(r.uniform(0.3, 0.7, (1, 9, 3, 3))),
                   f32(r.standard_normal((3, 2, 3, 3)) * 0.3)],
        diff=(0, 3), tol=4e-2),
    'pool2d': S(_std((1, 2, 6, 6)),
                attrs={'pool_size': 2, 'pool_type': 'avg',
                       'pool_stride': 2}),
    'pool3d': S(_std((1, 1, 4, 4, 4)),
                attrs={'pool_size': 2, 'pool_type': 'avg',
                       'pool_stride': 2}),
    'adaptive_pool2d': S(_std((1, 2, 6, 6)),
                         attrs={'pool_size': [3, 3], 'pool_type': 'avg'}),
    'adaptive_pool3d': S(_std((1, 1, 4, 4, 4)),
                         attrs={'pool_size': [2, 2, 2], 'pool_type': 'avg'}),
    'maxout': S(_std((2, 4, 3, 3)), attrs={'groups': 2}),
    'batch_norm': S(lambda r: [f32(r.standard_normal((2, 3, 4, 4))),
                               pos(r, (3,)), f32(r.standard_normal((3,))),
                               f32(r.standard_normal((3,)) * 0.1),
                               pos(r, (3,), 0.5, 1.5)], diff=(0, 1, 2)),
    'layer_norm': S(lambda r: [f32(r.standard_normal((3, 4))),
                               pos(r, (4,)), f32(r.standard_normal((4,)))],
                    diff=(0, 1, 2)),
    # -- the modern decoder block (ops/llm_ops.py) --
    'rms_norm': S(lambda r: [f32(r.standard_normal((3, 8))), pos(r, (8,))],
                  diff=(0, 1)),
    'rope': S(lambda r: [f32(r.standard_normal((1, 3, 2, 6))),
                         np.array([[0, 2, 5]])],
              attrs={'theta': 100.0, 'nope_dim': 2}),
    'lm_head': S(lambda r: [f32(r.standard_normal((3, 4))),
                            f32(r.standard_normal((4, 5)))], diff=(0, 1)),
    'swiglu_ffn': S(lambda r: [f32(r.standard_normal((3, 4))),
                               f32(r.standard_normal((4, 6)) * 0.5),
                               f32(r.standard_normal((4, 6)) * 0.5),
                               f32(r.standard_normal((6, 4)) * 0.5)],
                    diff=(0, 1, 2, 3)),
    'moe_experts': S(lambda r: [f32(r.standard_normal((5, 4))),
                                np.array([[0, 2], [2, 1], [0, 1], [2, 0],
                                          [2, 1]], np.int32),
                                pos(r, (5, 2)),
                                f32(r.standard_normal((4, 4, 6)) * 0.5),
                                f32(r.standard_normal((4, 4, 6)) * 0.5),
                                f32(r.standard_normal((4, 6, 4)) * 0.5)],
                     diff=(0, 2, 3, 4, 5)),
    'sigmoid_gate': S(lambda r: [f32(r.standard_normal((2, 3, 4))),
                                 f32(r.standard_normal((2, 3, 4)))],
                      diff=(0, 1)),
    'mla_prefill_attention': S(
        lambda r: [f32(r.standard_normal((1, 5, 2, 6))),
                   f32(r.standard_normal((1, 5, 10))),
                   f32(r.standard_normal((8, 2 * 7)) * 0.5)],
        diff=(0, 1, 2),
        attrs={'qk_nope_dim': 4, 'v_dim': 3, 'sm_scale': 0.4}),
    'retention_gate': S(lambda r: [f32(r.standard_normal((1, 3, 5))),
                                   f32(r.standard_normal((5, 2)))],
                        diff=(0, 1), attrs={'shift': 1.5}),
    # autodiff through the chunked scan (two chunks and a padded third);
    # no backward kernel of its own (ROADMAP R7)
    'power_retention_prefill': S(
        lambda r: [f32(r.standard_normal((1, 5, 2, 4))),
                   f32(r.standard_normal((1, 5, 1, 4))),
                   f32(r.standard_normal((1, 5, 1, 4))),
                   -pos(r, (1, 5, 1), 0.05, 0.5)],
        diff=(0, 1, 2, 3), attrs={'chunk': 2}),
    # the differentiable form of the gated short convolution: rows and taps
    'short_conv_prefill': S(
        lambda r: [f32(r.standard_normal((2, 5, 12))),
                   f32(r.standard_normal((3, 4)))],
        diff=(0, 1)),
    'instance_norm': S(lambda r: [f32(r.standard_normal((2, 3, 4, 4))),
                                  pos(r, (3,)),
                                  f32(r.standard_normal((3,)))],
                       diff=(0, 1, 2)),
    'group_norm': S(lambda r: [f32(r.standard_normal((2, 4, 3, 3))),
                               pos(r, (4,)), f32(r.standard_normal((4,)))],
                    diff=(0, 1, 2), attrs={'groups': 2}),
    'data_norm': S(lambda r: [f32(r.standard_normal((3, 4))),
                              f32(np.full((4,), 10.0)),
                              f32(r.standard_normal((4,))),
                              f32(np.full((4,), 10.0))], diff=(0,),
                   attrs={'is_test': True}),
    'spectral_norm': S(lambda r: [f32(r.standard_normal((4, 3)))],
                       tol=4e-2),
    'affine_channel': S(lambda r: [f32(r.standard_normal((2, 3, 4, 4))),
                                   pos(r, (3,)),
                                   f32(r.standard_normal((3,)))],
                        diff=(0, 1, 2)),
    'affine_grid': S(lambda r: [f32(r.standard_normal((2, 2, 3)) * 0.3)],
                     attrs={'out_shape': [2, 1, 4, 4]}),
    'grid_sampler': S(lambda r: [f32(r.standard_normal((1, 2, 4, 4))),
                                 f32(r.uniform(-0.8, 0.8, (1, 3, 3, 2)))],
                      diff=(0, 1), tol=4e-2),
    'interpolate': S(_std((1, 2, 4, 4)),
                     attrs={'out_shape': [8, 8], 'method': 'bilinear'}),
    'pixel_shuffle': S(_std((1, 4, 3, 3)), attrs={'upscale_factor': 2}),
    'unfold': S(_std((1, 2, 4, 4)), attrs={'kernel_sizes': 2}),
    'im2sequence': S(_std((1, 2, 4, 4)), attrs={'filter_size': 2}),
    'lrn': S(_std((1, 6, 3, 3))),
    'dropout': S(_std((3, 4)), attrs={'dropout_prob': 0.5, 'is_test': True}),
    'pad': S(_std((2, 3)), attrs={'paddings': [0, 1, 1, 0]}),
    'pad2d': S(_std((1, 2, 3, 3)), attrs={'paddings': [1, 1, 1, 1]}),
    'pad_constant_like': S(lambda r: [f32(r.standard_normal((4, 5))),
                                      f32(r.standard_normal((2, 3)))],
                           diff=(1,)),
    'lookup_table': S(lambda r: [f32(r.standard_normal((8, 4))),
                                 r.randint(0, 8, (3, 1)).astype(np.int64)]),
    'row_conv': S(lambda r: [f32(r.standard_normal((2, 5, 4))),
                             f32(r.standard_normal((3, 4)) * 0.3)],
                  diff=(0, 1)),
    'tree_conv': S(lambda r: [f32(r.standard_normal((1, 4, 3))),
                              r.randint(0, 3, (1, 3, 2)).astype(np.int64),
                              f32(r.standard_normal((3, 3, 2, 2)) * 0.3)],
                   diff=(0, 2)),
    'cvm': S(lambda r: [np.concatenate([pos(r, (3, 2), 1.0, 5.0),
                                        f32(r.standard_normal((3, 4)))], 1),
                        pos(r, (3, 2), 1.0, 5.0)], diff=(0,)),
    'temporal_shift': S(_std((4, 4, 3, 3)), attrs={'seg_num': 2}),
    'shuffle_channel': S(_std((1, 4, 3, 3)), attrs={'group': 2}),
    'space_to_depth': S(_std((1, 2, 4, 4)), attrs={'blocksize': 2}),
    'multiplex': S(lambda r: [np.asarray([0, 1, 0], np.int64),
                              [f32(r.standard_normal((3, 4))),
                               f32(r.standard_normal((3, 4)))]], diff=()),
    # --- rnn ---
    'lstm': S(lambda r: [f32(r.standard_normal((2, 3, 8)) * 0.3),
                         f32(r.standard_normal((2, 2)) * 0.3),
                         f32(r.standard_normal((2, 2)) * 0.3),
                         f32(r.standard_normal((2, 8)) * 0.3),
                         f32(r.standard_normal((8,)) * 0.1),
                         None, None, None], diff=(0, 3, 4)),
    'gru': S(lambda r: [f32(r.standard_normal((2, 3, 6)) * 0.3),
                        f32(r.standard_normal((2, 2)) * 0.3),
                        f32(r.standard_normal((2, 4)) * 0.3),
                        f32(r.standard_normal((2, 2)) * 0.3), None],
             diff=(0, 2, 3)),
    'gru_unit': S(lambda r: [f32(r.standard_normal((2, 6)) * 0.3),
                             f32(r.standard_normal((2, 2)) * 0.3),
                             f32(r.standard_normal((2, 6)) * 0.3), None],
                  diff=(0, 1, 2)),
    'lstm_unit': S(lambda r: [f32(r.standard_normal((2, 8)) * 0.3),
                              f32(r.standard_normal((2, 2)) * 0.3)],
                   diff=(0, 1)),
    # --- sequence (length-masked) ---
    'sequence_softmax': S(lambda r: [f32(r.standard_normal((2, 4))),
                                     np.asarray([3, 4], np.int64)]),
    'sequence_pool': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                  np.asarray([3, 4], np.int64)],
                       attrs={'pool_type': 'average'}),
    'sequence_pad': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                 f32([0.0]), np.asarray([3, 4], np.int64)]),
    'sequence_unpad': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                   np.asarray([3, 4], np.int64)]),
    'sequence_reverse': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                     np.asarray([3, 4], np.int64)]),
    'sequence_expand_as': S(lambda r: [f32(r.standard_normal((2, 3))),
                                       f32(r.standard_normal((2, 4, 3))),
                                       np.asarray([3, 4], np.int64)]),
    'sequence_conv': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                  f32(r.standard_normal((9, 5)) * 0.3),
                                  None, np.asarray([3, 4], np.int64)],
                       diff=(0, 1)),
    'sequence_reshape': S(lambda r: [f32(r.standard_normal((2, 4, 2))),
                                     np.asarray([4, 2], np.int64)],
                          attrs={'new_dim': 4}),
    'sequence_slice': S(lambda r: [f32(r.standard_normal((2, 4, 3))),
                                   np.asarray([[1], [0]], np.int64),
                                   np.asarray([[2], [3]], np.int64),
                                   np.asarray([4, 3], np.int64)]),
    'sequence_scatter': S(
        lambda r: [f32(r.standard_normal((2, 5))),
                   np.asarray([[0, 1, 2], [1, 2, 3]], np.int64),
                   f32(r.standard_normal((2, 3))),
                   np.asarray([3, 3], np.int64)], diff=(0, 2)),
    'sequence_concat': S(lambda r: [[f32(r.standard_normal((2, 3, 4))),
                                     f32(r.standard_normal((2, 2, 4)))],
                                    [np.asarray([3, 2], np.int64),
                                     np.asarray([2, 2], np.int64)]],
                         diff=()),
    'lod_reset': S(lambda r: [f32(r.standard_normal((2, 4))), None],
                   attrs={'target_lod': [2, 4]}),
    # --- tensor manipulation (linear: grads flow through gather/scatter) ---
    'concat': S(lambda r: [[f32(r.standard_normal((2, 3))),
                            f32(r.standard_normal((2, 3)))]], diff=()),
    'stack': S(lambda r: [[f32(r.standard_normal((2, 3))),
                           f32(r.standard_normal((2, 3)))]], diff=()),
    'split': S(_std((4, 6)), attrs={'num_or_sections': 2, 'dim': 1}),
    'unstack': S(_std((3, 4))),
    'reshape': S(_std((3, 4)), attrs={'shape': [4, 3]}),
    'transpose': S(_std((3, 4)), attrs={'perm': [1, 0]}),
    'transpose_batch_time': S(_std((3, 4, 2))),
    'flatten': S(_std((2, 3, 4))),
    'flatten2': S(_std((2, 3, 4))),
    'squeeze': S(_std((3, 1, 4))),
    'unsqueeze': S(_std((3, 4)), attrs={'axes': [1]}),
    'expand': S(_std((2, 3)), attrs={'expand_times': [2, 1]}),
    'expand_as': S(lambda r: [f32(r.standard_normal((1, 3))),
                              f32(r.standard_normal((4, 3)))]),
    'tile': S(_std((2, 3)), attrs={'repeat_times': [2, 2]}),
    'reverse': S(_std((3, 4)), attrs={'axis': [0]}),
    'slice': S(_std((4, 5)),
               attrs={'axes': [0, 1], 'starts': [1, 0], 'ends': [3, 4]}),
    'strided_slice': S(_std((4, 6)),
                       attrs={'axes': [1], 'starts': [0], 'ends': [6],
                              'strides': [2]}),
    'crop_tensor': S(_std((4, 5)),
                     attrs={'shape': [2, 3], 'offsets': [1, 1]}),
    'gather': S(lambda r: [f32(r.standard_normal((5, 3))),
                           np.asarray([0, 2, 4], np.int64)]),
    'gather_nd': S(lambda r: [f32(r.standard_normal((4, 3))),
                              np.asarray([[0], [2]], np.int64)]),
    'scatter': S(lambda r: [f32(r.standard_normal((5, 3))),
                            np.asarray([1, 3], np.int64),
                            f32(r.standard_normal((2, 3)))], diff=(0, 2)),
    'scatter_nd': S(lambda r: [np.asarray([[1], [3]], np.int64),
                               f32(r.standard_normal((2, 3)))], diff=(1,),
                    attrs={'shape': [5, 3]}),
    'scatter_nd_add': S(lambda r: [f32(r.standard_normal((5, 3))),
                                   np.asarray([[1], [3]], np.int64),
                                   f32(r.standard_normal((2, 3)))],
                        diff=(0, 2)),
    'where': S(lambda r: [r.rand(3, 4) < 0.5,
                          f32(r.standard_normal((3, 4))),
                          f32(r.standard_normal((3, 4)))], diff=(1, 2)),
    'top_k': S(lambda r: [f32(np.arange(12).reshape(3, 4)
                              + r.uniform(0, 0.3, (3, 4)))],
               attrs={'k': 2}),
    'diag': S(_std((4,))),
    'matrix_diag_part': S(_std((3, 3))),
    'assign': S(_std((3, 4))),
    'cast': S(_std((3, 4)), attrs={'dtype': 'float32'}),
    'fill_zeros_like': S(_std((3, 4))),
    # --- detection (differentiable heads) ---
    'roi_align': S(lambda r: [f32(r.standard_normal((1, 2, 6, 6))),
                              f32([[0.5, 0.5, 4.0, 4.0]]),
                              np.asarray([0], np.int64)],
                   attrs={'pooled_height': 2, 'pooled_width': 2},
                   tol=4e-2),
    'roi_pool': S(lambda r: [f32(r.standard_normal((1, 2, 6, 6))),
                             f32([[0.5, 0.5, 4.0, 4.0]]),
                             np.asarray([0], np.int64)],
                  attrs={'pooled_height': 2, 'pooled_width': 2}),
    'prroi_pool': S(lambda r: [f32(r.standard_normal((1, 2, 6, 6))),
                               f32([[0.5, 0.5, 4.0, 4.0]]),
                               np.asarray([0], np.int64)],
                    attrs={'pooled_height': 2, 'pooled_width': 2},
                    tol=4e-2),
    'psroi_pool': S(lambda r: [f32(r.standard_normal((1, 4, 6, 6))),
                               f32([[0.5, 0.5, 4.0, 4.0]]),
                               np.asarray([0], np.int64)],
                    attrs={'output_channels': 1, 'pooled_height': 2,
                           'pooled_width': 2}, tol=4e-2),
    'yolov3_loss': S(
        lambda r: [f32(r.standard_normal((1, 12, 4, 4)) * 0.3),
                   f32(r.uniform(0.2, 0.6, (1, 2, 4))),
                   r.randint(0, 1, (1, 2)).astype(np.int64),
                   f32(np.ones((1, 2)))],
        attrs={'anchors': [10, 13, 16, 30], 'anchor_mask': [0, 1],
               'class_num': 1, 'use_label_smooth': False}, tol=5e-2),
    'box_encode_per_row': S(lambda r: [f32([[1., 1., 4., 4.]]),
                                       f32([[1.5, 1.5, 4.5, 4.5]])],
                            diff=(0, 1), tol=4e-2),
    'iou_similarity': S(lambda r: [f32([[1., 1., 4., 4.]]),
                                   f32([[2., 2., 5., 5.]])], diff=(0, 1),
                        tol=4e-2),
    'box_clip': S(lambda r: [f32([[[1., 1., 4., 4.]]]),
                             f32([[8., 8., 1.]])], diff=(0,)),
    # linear map: central difference is exact for any eps; the large eps
    # suppresses f32 cancellation noise from the big positional base values
    'polygon_box_transform': S(_std((1, 8, 3, 3)), eps=0.5, tol=4e-2),
}
# ---------------------------------------------------------------------------
# explicitly nondifferentiable / not-gradient-tested ops, with reasons
# ---------------------------------------------------------------------------
NONDIFF = {
    # integer / boolean outputs
    'arg_max': 'integer index output', 'arg_min': 'integer index output',
    'argsort': 'permutation/index output',
    'equal': 'boolean output', 'not_equal': 'boolean output',
    'less_than': 'boolean output', 'less_equal': 'boolean output',
    'greater_than': 'boolean output', 'greater_equal': 'boolean output',
    'logical_and': 'boolean output', 'logical_or': 'boolean output',
    'logical_xor': 'boolean output', 'logical_not': 'boolean output',
    'is_empty': 'boolean output', 'isfinite': 'boolean output',
    'has_inf': 'boolean output', 'has_nan': 'boolean output',
    'one_hot': 'integer input / constant output',
    'sequence_mask': 'integer mask output',
    'sequence_enumerate': 'integer id output',
    'shape': 'metadata output', 'rank': 'metadata output',
    'size': 'metadata output',
    'shard_index': 'integer id output', 'hash': 'integer hash output',
    'sign': 'piecewise-constant (zero gradient)',
    'ceil': 'piecewise-constant (zero gradient)',
    'floor': 'piecewise-constant (zero gradient)',
    'round': 'piecewise-constant (zero gradient)',
    'elementwise_floordiv': 'integer/piecewise-constant output',
    'unique_with_counts': 'integer index/count outputs',
    'where_index': 'integer index output',
    'mean_iou': 'confusion-matrix counting (integer)',
    'accuracy': 'metric (integer comparison)',
    'auc': 'metric (threshold counting)',
    'chunk_eval': 'metric (span counting)',
    'detection_map': 'metric (greedy integer matching)',
    'edit_distance': 'integer distance',
    'similarity_focus': 'binary mask output (argmax selection)',
    # constant / generator ops
    'fill_constant': 'constant output',
    'fill_constant_batch_size_like': 'constant output',
    'fill_any_like': 'constant output', 'eye': 'constant output',
    'linspace': 'constant output', 'range': 'constant output',
    'gaussian_random': 'random generator',
    'gaussian_random_batch_size_like': 'random generator',
    'uniform_random': 'random generator',
    'uniform_random_batch_size_like': 'random generator',
    'truncated_gaussian_random': 'random generator',
    'randint': 'random integer generator',
    'randperm': 'random permutation generator',
    'sampling_id': 'stochastic id sampling',
    'random_crop': 'stochastic crop (index selection)',
    'shuffle_batch': 'stochastic permutation',
    'nce': 'stochastic negative sampling (loss checked in layer tests)',
    'dpsgd': 'stochastic update op (noise injection)',
    # optimizer update ops — golden-value tested in test_optimizers.py
    'sgd': 'optimizer update (golden-tested)',
    'momentum': 'optimizer update (golden-tested)',
    'lars_momentum': 'optimizer update (golden-tested)',
    'adam': 'optimizer update (golden-tested)',
    'adamax': 'optimizer update (golden-tested)',
    'adagrad': 'optimizer update (golden-tested)',
    'decayed_adagrad': 'optimizer update (golden-tested)',
    'adadelta': 'optimizer update (golden-tested)',
    'rmsprop': 'optimizer update (golden-tested)',
    'ftrl': 'optimizer update (golden-tested)',
    'lamb': 'optimizer update (golden-tested)',
    'dgc_momentum': 'optimizer update (golden-tested)',
    'fused_sgd': 'multi-tensor optimizer update (bitwise parity vs per-'
                 'param sgd in test_ir_passes.py)',
    'fused_momentum': 'multi-tensor optimizer update (bitwise parity vs '
                      'per-param momentum in test_ir_passes.py)',
    'fused_lars_momentum': 'multi-tensor optimizer update (bitwise parity '
                           'vs per-param lars_momentum in '
                           'test_fleet_runtime.py)',
    'fused_adam': 'multi-tensor optimizer update (bitwise parity vs per-'
                  'param adam in test_ir_passes.py)',
    'sparse_sgd': 'rows-only optimizer update (parity vs dense sgd in '
                  'tests/ops/test_sparse_ops.py)',
    'sparse_momentum': 'rows-only optimizer update (parity vs dense '
                       'momentum in tests/ops/test_sparse_ops.py)',
    'sparse_adagrad': 'rows-only optimizer update (parity vs dense '
                      'adagrad in tests/ops/test_sparse_ops.py)',
    'sparse_adam': 'rows-only lazy optimizer update (parity vs dense '
                   'adam in tests/ops/test_sparse_ops.py)',
    'check_finite_and_unscale': 'AMP bookkeeping (tested in test_amp.py)',
    'update_loss_scaling': 'AMP bookkeeping (tested in test_amp.py)',
    # control-flow / array plumbing
    '__array_length__': 'TensorArray plumbing',
    '__array_read__': 'TensorArray plumbing',
    '__array_write__': 'TensorArray plumbing',
    'print': 'side-effect op',
    'c_sync_calc_stream': 'no-op stream sync',
    'c_sync_comm_stream': 'no-op stream sync',
    # collectives need a mesh/shard_map context
    'c_allreduce_sum': 'collective (tested in test_parallel.py)',
    'c_allreduce_sum_bucket': 'collective (bucketed gradient sync — '
                              'tested in test_bucket_allreduce.py / '
                              'test_quant_collectives.py)',
    'c_allreduce_max': 'collective (tested in test_parallel.py)',
    'c_allreduce_min': 'collective (tested in test_parallel.py)',
    'c_allreduce_prod': 'collective (tested in test_parallel.py)',
    'c_allgather': 'collective (tested in test_parallel.py)',
    'c_broadcast': 'collective (tested in test_parallel.py)',
    'c_reducescatter': 'collective (tested in test_parallel.py)',
    # selection / assignment ops with index outputs (forward-tested in
    # tests/ops/test_detection_ops.py)
    'anchor_generator': 'constant anchor grid',
    'prior_box': 'constant prior grid',
    'density_prior_box': 'constant prior grid',
    'bipartite_match': 'integer matching',
    'box_coder': 'box transform (forward-tested; encode uses log/div of '
                 'constant priors)',
    'box_decoder_and_assign': 'argmax assignment',
    'multiclass_nms': 'index selection (NMS)',
    'locality_aware_nms': 'index selection (NMS)',
    'generate_proposals': 'index selection (NMS)',
    'collect_fpn_proposals': 'index selection (top-k)',
    'distribute_fpn_proposals': 'integer level routing',
    'rpn_target_assign': 'integer target assignment',
    'retinanet_target_assign': 'integer target assignment',
    'target_assign': 'integer target assignment',
    'ssd_loss': 'internally uses integer matching; forward-tested',
    'yolo_box': 'inference-only box decode',
    'roi_perspective_transform': 'integer mask output dominates',
    'deformable_roi_pooling': 'forward-tested (sampling indices)',
    'crf_decoding': 'integer viterbi path',
    'ctc_greedy_decoder': 'integer decode',
    'beam_search_step': 'integer beam selection',
    'gather_tree': 'integer beam backtrace',
    'filter_by_instag': 'integer filtering',
    'get_tensor_from_selected_rows': 'identity plumbing',
    'merge_selected_rows': 'identity plumbing',
    'quantize_linear': 'integer quantized output',
    'dequantize_linear': 'paired with quantize_linear',
    'fake_quantize_dequantize_abs_max':
        'STE surrogate gradient (intentionally differs from numeric diff; '
        'QAT path tested in test_inference.py)',
    'fake_channel_wise_quantize_dequantize_abs_max':
        'STE surrogate gradient',
    'fake_quantize_dequantize_moving_average_abs_max':
        'STE surrogate gradient',
    'reduce_all': 'boolean output', 'reduce_any': 'boolean output',
    'paged_attention':
        'inference-only decode-phase cache read (serving/decode/); training '
        'gradients flow through whole-sequence attention, parity tested in '
        'tests/ops/test_paged_attention.py',
    'moe_router':
        'integer expert ids and a top-k choice that is piecewise constant; '
        'the weights\' gradient belongs to the training of the block '
        '(ROADMAP R5); forward tested in tests/framework/'
        'test_latent_moe_lm.py',
    'diffusion_pick':
        'integer token ids and the probability of an argmax, read by the '
        'host\'s unmasking schedule (serving/decode/diffusion.py); '
        'inference-only; forward tested in tests/ops/test_block_read.py',
    'mla_decode_attention':
        'inference-only absorbed read of the paged latent cache '
        '(serving/decode/); training gradients flow through '
        'mla_prefill_attention, parity tested in tests/framework/'
        'test_latent_moe_lm.py',
    'power_retention_step':
        'inference-only update-and-read of the recurrent state cache '
        '(serving/decode/); training gradients flow through '
        'power_retention_prefill, parity tested in tests/ops/'
        'test_power_retention.py',
    'short_conv_step':
        'inference-only shift-and-filter of the conv state cache '
        '(serving/decode/); training gradients flow through '
        'short_conv_prefill, parity tested in tests/ops/test_short_conv.py',
    'paged_prefill_attention':
        'inference-only prefill-phase cache read (serving/decode/); '
        'parity tested in tests/ops/test_paged_attention.py',
}



def test_registry_fully_classified():
    """Every registered op is either gradient-checked or has a reason."""
    names = set(_REGISTRY)
    specs = set(GRAD_SPECS)
    nd = set(NONDIFF)
    unknown = (specs | nd) - names
    assert not unknown, f"classified but not registered: {sorted(unknown)}"
    both = specs & nd
    assert not both, f"doubly classified: {sorted(both)}"
    missing = names - specs - nd
    assert not missing, (
        f"ops with no gradient classification: {sorted(missing)} — add a "
        f"GRAD_SPECS entry or a NONDIFF reason")


def _scalarize(res):
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(res):
        leaf = jnp.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            total = total + jnp.sum(leaf.astype(jnp.float32))
    return total


@pytest.mark.parametrize('name', sorted(GRAD_SPECS))
def test_check_grad(name):
    spec = GRAD_SPECS[name]
    opdef = get_op(name)
    rng = R(0)
    args = spec['args'](rng)
    attrs = dict(spec['attrs'])
    if opdef.needs_rng:
        attrs['key'] = jax.random.PRNGKey(0)
    diff = spec['diff']
    if not diff:
        # variadic-input op: differentiate the first element of the first
        # list-valued argument
        li = next(i for i, a in enumerate(args) if isinstance(a, list))

        def f(first):
            full = list(args)
            lst = list(full[li])
            lst[0] = first
            full[li] = lst
            return _scalarize(opdef.fn(*full, **attrs))
        dargs = (jnp.asarray(args[li][0]),)
    else:
        def f(*dargs):
            full = list(args)
            for i, d in zip(diff, dargs):
                full[i] = d
            return _scalarize(opdef.fn(*full, **attrs))
        dargs = tuple(jnp.asarray(args[i]) for i in diff)
    tol = spec['tol']
    check_grads(f, dargs, order=1, modes=['rev'], atol=tol, rtol=tol,
                eps=spec['eps'])
