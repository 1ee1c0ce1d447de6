"""Static per-op shape/dtype inference over the Program IR — zero tracing.

The executor lowers a Program through jax, so shape errors normally
surface as XLA trace failures with no pointer back to the op that
caused them. This module re-derives every var's ``VarInfo(shape, dtype,
lod_level)`` from op semantics alone: an :func:`infer_rule` registry maps
op types to small pure functions mirroring the registered kernel's
shape/dtype arithmetic (ops/*.py), and :func:`infer_block` propagates
infos op-by-op through a block.

Lattice: a dim is either a concrete ``int`` or :data:`UNKNOWN` (dynamic
batch dims, declared ``-1`` dims). A whole shape may be ``None`` (rank
unknown), and a dtype may be ``None``. Every rule treats UNKNOWN as
"compatible with anything" — dynamic dims never poison the analysis and
never produce false mismatches; only provably-inconsistent programs
raise :class:`InferError`.

Rules cover every op type the tier-1 recipes emit (elementwise /
broadcast, matmul / conv, reductions, reshape / concat / split, norms,
losses, the ``fused_*`` ops and ``c_allreduce_*``). Ops without a rule
propagate their declared var infos and are reported as ``no-infer-rule``
info diagnostics by checks.py — unknown ops degrade coverage, never
correctness.

Adding a rule (docs/ANALYSIS.md has the walkthrough)::

    @infer_rule('my_op')
    def _my_op(ctx):
        x = ctx.input('x')                  # VarInfo of the first 'x' name
        return {'Out': VarInfo(x.shape, x.dtype)}
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

__all__ = ['UNKNOWN', 'VarInfo', 'InferError', 'infer_rule', 'has_rule',
           'all_rules', 'OpCtx', 'infer_op', 'seed_env', 'declared_info']


class _UnknownDim:
    """Singleton lattice value for a statically-unknown dimension."""
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self):
        return '?'

    def __reduce__(self):
        return (_UnknownDim, ())


UNKNOWN = _UnknownDim()


def known(dim) -> bool:
    return dim is not UNKNOWN and dim is not None


def dims_agree(a, b) -> bool:
    """Whether two dims can be equal (UNKNOWN agrees with anything)."""
    return not (known(a) and known(b)) or a == b


def merge_dim(a, b):
    return a if known(a) else b


class VarInfo:
    """Static facts about one var: shape (tuple of int/UNKNOWN, or None =
    rank unknown), canonical dtype name (or None), lod_level."""

    __slots__ = ('shape', 'dtype', 'lod_level')

    def __init__(self, shape=None, dtype=None, lod_level=0):
        if shape is not None:
            shape = tuple(UNKNOWN if (s is None or s is UNKNOWN
                                      or (isinstance(s, int) and s < 0))
                          else int(s) for s in shape)
        self.shape = shape
        self.dtype = dtype
        self.lod_level = lod_level

    @property
    def ndim(self):
        return None if self.shape is None else len(self.shape)

    def numel(self):
        """Element count, or None when any dim is unknown."""
        if self.shape is None or any(not known(s) for s in self.shape):
            return None
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    def with_dtype(self, dtype):
        return VarInfo(self.shape, dtype, self.lod_level)

    def display_shape(self):
        """Shape with UNKNOWN rendered as -1 (fluid display convention)."""
        if self.shape is None:
            return None
        return tuple(-1 if not known(s) else s for s in self.shape)

    def __repr__(self):
        return f'VarInfo(shape={self.shape}, dtype={self.dtype})'


def shapes_agree(a: VarInfo, b: VarInfo) -> bool:
    """Whether two infos' shapes can denote the same array."""
    if a.shape is None or b.shape is None:
        return True
    if len(a.shape) != len(b.shape):
        return False
    return all(dims_agree(x, y) for x, y in zip(a.shape, b.shape))


class InferError(Exception):
    """A rule proved the op inconsistent. `kind` picks the diagnostic
    code: 'shape-mismatch', 'dtype-mismatch', or 'bad-attr'."""

    def __init__(self, message, kind='shape-mismatch'):
        super().__init__(message)
        self.kind = kind


def declared_info(var) -> VarInfo:
    """VarInfo from a framework.Variable declaration."""
    return VarInfo(var.shape, var.dtype, getattr(var, 'lod_level', 0) or 0)


def seed_env(program) -> Dict[str, VarInfo]:
    """Initial env for global-block inference: every declared var whose
    value exists before any op runs — data (feed) vars and persistables
    (scope state) — mapped to its declared info."""
    env = {}
    for v in program.list_vars():
        if v.is_data or v.persistable:
            env[v.name] = declared_info(v)
    return env


# ---------------------------------------------------------------------------
# dtype lattice helpers
# ---------------------------------------------------------------------------

def promote(a: Optional[str], b: Optional[str]) -> Optional[str]:
    """jnp-style promotion over canonical dtype names; None is absorbing."""
    if a is None or b is None:
        return None
    if a == b:
        return a
    import jax.numpy as jnp
    from ..core.dtypes import convert_dtype, _NAME_TO_DTYPE
    try:
        return convert_dtype(jnp.promote_types(_NAME_TO_DTYPE[a],
                                               _NAME_TO_DTYPE[b]))
    except Exception:
        return None


def is_float(dtype: Optional[str]) -> Optional[bool]:
    if dtype is None:
        return None
    from ..core.dtypes import FLOAT_DTYPES
    return dtype in FLOAT_DTYPES


# ---------------------------------------------------------------------------
# shape arithmetic
# ---------------------------------------------------------------------------

def broadcast_shapes(a, b, what='operands'):
    """Numpy-style broadcast under the UNKNOWN lattice. Raises InferError
    only when two KNOWN dims are unequal and neither is 1."""
    if a is None or b is None:
        return None
    out = []
    ra, rb = list(a)[::-1], list(b)[::-1]
    for i in range(max(len(ra), len(rb))):
        da = ra[i] if i < len(ra) else 1
        db = rb[i] if i < len(rb) else 1
        if known(da) and known(db):
            if da != db and da != 1 and db != 1:
                raise InferError(
                    f'{what} are not broadcast-compatible: '
                    f'{tuple(a)} vs {tuple(b)} (dim {da} vs {db})')
            out.append(max(da, db))
        elif known(da) and da != 1:
            out.append(da)
        elif known(db) and db != 1:
            out.append(db)
        else:
            out.append(UNKNOWN)
    return tuple(out[::-1])


def paddle_broadcast(x: VarInfo, y: VarInfo, axis=-1):
    """Mirror ops.math_ops._align_y: paddle elementwise aligns y at `axis`
    of x by appending trailing 1-dims, then broadcasts."""
    xs, ys = x.shape, y.shape
    if xs is None or ys is None:
        return None
    if len(ys) == 0 or xs == ys or len(ys) >= len(xs):
        return broadcast_shapes(xs, ys)
    ax = len(xs) - len(ys) if axis in (-1, None) else axis
    trailing = len(xs) - ax - len(ys)
    if trailing < 0:
        raise InferError(
            f'elementwise axis={axis} places y{tuple(ys)} past the end '
            f'of x{tuple(xs)}', kind='bad-attr')
    return broadcast_shapes(xs, ys + (1,) * trailing)


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

_RULES: Dict[str, object] = {}


def infer_rule(*op_types):
    """Decorator: register one inference rule for the given op types. The
    rule receives an :class:`OpCtx` and returns {output_slot: VarInfo |
    [VarInfo]} (missing slots default to unknown)."""

    def deco(fn):
        for t in op_types:
            if t in _RULES:
                raise ValueError(f'infer rule for {t!r} registered twice')
            _RULES[t] = fn
        return fn

    return deco


def has_rule(op_type: str) -> bool:
    return op_type in _RULES


def all_rules():
    return dict(_RULES)


class OpCtx:
    """What a rule may consult about one op: input infos resolved through
    the flow env (falling back to var declarations) and the op's attrs."""

    def __init__(self, op, env: Dict[str, VarInfo], block):
        self.op = op
        self.env = env
        self.block = block

    def info_of(self, name: str) -> VarInfo:
        if name in self.env:
            return self.env[name]
        if self.block is not None and self.block.has_var(name):
            return declared_info(self.block.var(name))
        return VarInfo()

    def inputs(self, slot: str) -> List[VarInfo]:
        return [self.info_of(n) for n in self.op.inputs.get(slot, [])]

    def input(self, slot: str) -> Optional[VarInfo]:
        names = self.op.inputs.get(slot, [])
        return self.info_of(names[0]) if names else None

    def require(self, slot: str) -> VarInfo:
        v = self.input(slot)
        if v is None:
            raise InferError(f'required input slot {slot!r} is empty',
                             kind='bad-attr')
        return v

    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    def require_attr(self, name):
        if name not in self.op.attrs:
            raise InferError(f'required attr {name!r} is missing',
                             kind='bad-attr')
        return self.op.attrs[name]


def infer_op(op, env: Dict[str, VarInfo], block) -> Optional[Dict]:
    """Run the rule for `op`. Returns {slot: VarInfo|[VarInfo]} or None
    when no rule is registered. Raises InferError on proven
    inconsistency."""
    rule = _RULES.get(op.type)
    if rule is None:
        return None
    return rule(OpCtx(op, env, block))


# ---------------------------------------------------------------------------
# rules: elementwise / unary / comparisons
# ---------------------------------------------------------------------------

_ELTWISE_BINARY = ('elementwise_add', 'elementwise_sub', 'elementwise_mul',
                   'elementwise_div', 'elementwise_max', 'elementwise_min',
                   'elementwise_pow', 'elementwise_mod',
                   'elementwise_floordiv')


@infer_rule(*_ELTWISE_BINARY)
def _eltwise(ctx):
    x, y = ctx.require('x'), ctx.require('y')
    shape = paddle_broadcast(x, y, ctx.attr('axis', -1))
    return {'Out': VarInfo(shape, promote(x.dtype, y.dtype))}


@infer_rule('fused_elemwise_add_activation')
def _fused_add_act(ctx):
    functor = ctx.attr('functor', 'relu')
    if functor not in ('relu', 'sigmoid', 'tanh'):
        raise InferError(f'unknown functor {functor!r} for '
                         f'fused_elemwise_add_activation', kind='bad-attr')
    x, y = ctx.require('x'), ctx.require('y')
    shape = paddle_broadcast(x, y, ctx.attr('axis', -1))
    return {'Out': VarInfo(shape, promote(x.dtype, y.dtype))}


_SAME_SHAPE_UNARY = (
    'relu', 'sigmoid', 'tanh', 'exp', 'sqrt', 'rsqrt', 'abs', 'ceil',
    'floor', 'cos', 'sin', 'acos', 'asin', 'cosh', 'sinh', 'round',
    'reciprocal', 'log', 'square', 'softplus', 'softsign', 'sign', 'erf',
    'logsigmoid', 'atan', 'tanh_shrink', 'gelu', 'leaky_relu', 'relu6',
    'elu', 'selu', 'brelu', 'soft_relu', 'stanh', 'hard_sigmoid',
    'hard_swish', 'swish', 'hard_shrink', 'softshrink', 'thresholded_relu',
    'scale', 'clip', 'clip_by_norm', 'increment', 'assign',
    'fill_zeros_like', 'pow', 'l2_normalize')


@infer_rule(*_SAME_SHAPE_UNARY)
def _unary(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('prelu')
def _prelu(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('softmax', 'log_softmax')
def _softmax(ctx):
    x = ctx.require('x')
    ax = ctx.attr('axis', -1)
    if x.shape is not None and isinstance(ax, int) \
            and not -len(x.shape) <= ax < len(x.shape):
        raise InferError(f'softmax axis {ax} out of range for '
                         f'rank-{len(x.shape)} input', kind='bad-attr')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('dropout')
def _dropout(ctx):
    x = ctx.require('x')
    p = ctx.attr('dropout_prob', 0.5)
    if not isinstance(p, (int, float)) or not 0.0 <= float(p) <= 1.0:
        raise InferError(f'dropout_prob must be in [0, 1], got {p!r}',
                         kind='bad-attr')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('cast')
def _cast(ctx):
    x = ctx.require('x')
    from ..core.dtypes import convert_dtype
    try:
        dtype = convert_dtype(ctx.require_attr('dtype'))
    except TypeError as e:
        raise InferError(str(e), kind='bad-attr')
    return {'Out': VarInfo(x.shape, dtype)}


_COMPARE = ('equal', 'not_equal', 'less_than', 'less_equal', 'greater_than',
            'greater_equal', 'logical_and', 'logical_or', 'logical_xor')


@infer_rule(*_COMPARE)
def _compare(ctx):
    x, y = ctx.require('x'), ctx.require('y')
    shape = (broadcast_shapes(x.shape, y.shape)
             if x.shape is not None and y.shape is not None else None)
    return {'Out': VarInfo(shape, 'bool')}


@infer_rule('logical_not', 'isfinite', 'has_inf', 'has_nan')
def _bool_unary(ctx):
    x = ctx.require('x')
    if ctx.op.type == 'logical_not':
        return {'Out': VarInfo(x.shape, 'bool')}
    return {'Out': VarInfo((), 'bool')}


# ---------------------------------------------------------------------------
# rules: matmul family / reductions
# ---------------------------------------------------------------------------

@infer_rule('matmul')
def _matmul(ctx):
    x, y = ctx.require('x'), ctx.require('y')
    if x.shape is None or y.shape is None:
        return {'Out': VarInfo(None, promote(x.dtype, y.dtype))}
    xs = list(x.shape)
    ys = list(y.shape)
    if ctx.attr('transpose_x', False) and len(xs) > 1:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if ctx.attr('transpose_y', False) and len(ys) > 1:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if not xs or not ys:
        raise InferError('matmul operands must have rank >= 1')
    if len(xs) == 1 and len(ys) == 1:
        if not dims_agree(xs[0], ys[0]):
            raise InferError(f'matmul contraction dims differ: '
                             f'{xs[0]} vs {ys[0]}')
        return {'Out': VarInfo((), promote(x.dtype, y.dtype))}
    k_x = xs[-1]
    k_y = ys[-2] if len(ys) >= 2 else ys[0]
    if not dims_agree(k_x, k_y):
        raise InferError(
            f'matmul contraction dims differ: x{tuple(x.shape)} '
            f'(K={k_x}) vs y{tuple(y.shape)} (K={k_y})')
    if len(ys) == 1:
        out = tuple(xs[:-1])
    elif len(xs) == 1:
        out = tuple(ys[:-2] + ys[-1:])
    else:
        batch = broadcast_shapes(tuple(xs[:-2]), tuple(ys[:-2]),
                                 'matmul batch dims')
        out = (None if batch is None
               else batch + (xs[-2], ys[-1]))
    return {'Out': VarInfo(out, promote(x.dtype, y.dtype))}


@infer_rule('mul')
def _mul(ctx):
    x, y = ctx.require('x'), ctx.require('y')
    xcd = ctx.attr('x_num_col_dims', 1)
    ycd = ctx.attr('y_num_col_dims', 1)
    if x.shape is None or y.shape is None:
        return {'Out': VarInfo(None, promote(x.dtype, y.dtype))}
    xs, ys = x.shape, y.shape
    if not 0 < xcd < max(len(xs), 1) + 1 or ycd < 1 or ycd > len(ys):
        raise InferError(
            f'mul x_num_col_dims={xcd}/y_num_col_dims={ycd} invalid for '
            f'x{tuple(xs)} y{tuple(ys)}', kind='bad-attr')

    def prod(dims):
        if any(not known(d) for d in dims):
            return UNKNOWN
        return int(np.prod(dims, dtype=np.int64)) if dims else 1

    k_x, k_y = prod(xs[xcd:]), prod(ys[:ycd])
    if not dims_agree(k_x, k_y):
        raise InferError(
            f'mul inner dims differ: x{tuple(xs)} flattens to K={k_x}, '
            f'y{tuple(ys)} to K={k_y}')
    return {'Out': VarInfo(tuple(xs[:xcd]) + tuple(ys[ycd:]),
                           promote(x.dtype, y.dtype))}


@infer_rule('dot')
def _dot(ctx):
    x, y = ctx.require('x'), ctx.require('y')
    if x.shape is not None and y.shape is not None \
            and not shapes_agree(x, y):
        raise InferError(f'dot operands differ: {x.shape} vs {y.shape}')
    return {'Out': VarInfo((1,), promote(x.dtype, y.dtype))}


def _reduced_shape(shape, dim, keep_dim, reduce_all):
    if shape is None:
        return None
    nd = len(shape)
    if reduce_all or dim is None:
        axes = tuple(range(nd))
    else:
        axes = (dim,) if isinstance(dim, int) else tuple(dim)
        for a in axes:
            if not -nd <= a < nd:
                raise InferError(f'reduce dim {a} out of range for '
                                 f'rank-{nd} input', kind='bad-attr')
        axes = tuple(a % nd for a in axes)
    if keep_dim:
        return tuple(1 if i in axes else s for i, s in enumerate(shape))
    return tuple(s for i, s in enumerate(shape) if i not in axes)


_REDUCES = ('reduce_sum', 'reduce_mean', 'reduce_max', 'reduce_min',
            'reduce_prod', 'reduce_all', 'reduce_any')


@infer_rule(*_REDUCES)
def _reduce(ctx):
    x = ctx.require('x')
    shape = _reduced_shape(x.shape, ctx.attr('dim'),
                           ctx.attr('keep_dim', False),
                           ctx.attr('reduce_all', False))
    dtype = 'bool' if ctx.op.type in ('reduce_all', 'reduce_any') else x.dtype
    return {'Out': VarInfo(shape, dtype)}


@infer_rule('logsumexp')
def _logsumexp(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo(_reduced_shape(x.shape, ctx.attr('dim'),
                                          ctx.attr('keep_dim', False),
                                          False), x.dtype)}


@infer_rule('mean')
def _mean(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo((), x.dtype)}


@infer_rule('cumsum')
def _cumsum(ctx):
    x = ctx.require('x')
    if ctx.attr('axis') is None or ctx.attr('flatten', False):
        n = x.numel()
        return {'Out': VarInfo((n if n is not None else UNKNOWN,), x.dtype)}
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('sum')
def _sum_variadic(ctx):
    xs = ctx.inputs('xs')
    if not xs:
        raise InferError('sum needs at least one input', kind='bad-attr')
    out = xs[0]
    for x in xs[1:]:
        if not shapes_agree(out, x):
            raise InferError(
                f'sum operands have incompatible shapes: '
                f'{out.shape} vs {x.shape}')
        out = VarInfo(out.shape if out.shape is not None else x.shape,
                      promote(out.dtype, x.dtype))
    return {'Out': out}


# ---------------------------------------------------------------------------
# rules: shape manipulation
# ---------------------------------------------------------------------------

@infer_rule('reshape')
def _reshape(ctx):
    x = ctx.require('x')
    spec = list(ctx.require_attr('shape'))
    if spec.count(-1) > 1:
        raise InferError(f'reshape shape {spec} has more than one -1',
                         kind='bad-attr')
    out = []
    for i, s in enumerate(spec):
        if s == 0:                      # paddle: copy input dim i
            if x.shape is None or i >= len(x.shape):
                out.append(UNKNOWN)
            else:
                out.append(x.shape[i])
        elif s == -1:
            out.append(UNKNOWN)         # refined below when provable
        elif isinstance(s, int) and s > 0:
            out.append(s)
        else:
            raise InferError(f'reshape shape entry {s!r} invalid',
                             kind='bad-attr')
    n_in = x.numel()
    if -1 in spec:
        rest = [d for d in out if known(d)]
        if len(rest) == len(out) - 1 and n_in is not None:
            prod = int(np.prod(rest, dtype=np.int64)) if rest else 1
            if prod == 0 or n_in % prod != 0:
                raise InferError(
                    f'reshape cannot infer -1: {n_in} elements do not '
                    f'divide into {spec}')
            out[out.index(UNKNOWN)] = n_in // prod
    elif n_in is not None and all(known(d) for d in out):
        n_out = int(np.prod(out, dtype=np.int64)) if out else 1
        if n_in != n_out:
            raise InferError(
                f'reshape changes element count: {x.display_shape()} '
                f'({n_in} elems) -> {spec} ({n_out} elems)')
    return {'Out': VarInfo(tuple(out), x.dtype)}


@infer_rule('transpose')
def _transpose(ctx):
    x = ctx.require('x')
    perm = list(ctx.require_attr('perm'))
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    if sorted(p % len(perm) for p in perm) != list(range(len(x.shape))):
        raise InferError(
            f'transpose perm {perm} is not a permutation of rank '
            f'{len(x.shape)}', kind='bad-attr')
    return {'Out': VarInfo(tuple(x.shape[p] for p in perm), x.dtype)}


@infer_rule('squeeze')
def _squeeze(ctx):
    x = ctx.require('x')
    axes = ctx.attr('axes') or None
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    nd = len(x.shape)
    if not axes:
        out = tuple(s for s in x.shape if not (known(s) and s == 1))
    else:
        axes = {a % nd for a in axes}
        for a in axes:
            if known(x.shape[a]) and x.shape[a] != 1:
                raise InferError(
                    f'squeeze axis {a} has size {x.shape[a]} != 1',
                    kind='bad-attr')
        out = tuple(s for i, s in enumerate(x.shape) if i not in axes)
    return {'Out': VarInfo(out, x.dtype)}


@infer_rule('unsqueeze')
def _unsqueeze(ctx):
    x = ctx.require('x')
    axes = ctx.require_attr('axes')
    axes = [axes] if isinstance(axes, int) else list(axes)
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    out = list(x.shape)
    for a in sorted(axes):
        out.insert(a if a >= 0 else a + len(out) + 1, 1)
    return {'Out': VarInfo(tuple(out), x.dtype)}


@infer_rule('concat')
def _concat(ctx):
    xs = ctx.inputs('xs')
    if not xs:
        raise InferError('concat needs at least one input', kind='bad-attr')
    axis = ctx.attr('axis', 0)
    dtype = xs[0].dtype
    for x in xs[1:]:
        dtype = promote(dtype, x.dtype)
    ranks = {len(x.shape) for x in xs if x.shape is not None}
    if len(ranks) > 1:
        raise InferError(f'concat inputs have different ranks: {ranks}')
    if not ranks:
        return {'Out': VarInfo(None, dtype)}
    nd = ranks.pop()
    if not -nd <= axis < nd:
        raise InferError(f'concat axis {axis} out of range for rank {nd}',
                         kind='bad-attr')
    axis %= nd
    out = [UNKNOWN] * nd
    cat = 0                      # becomes UNKNOWN on the first unknown part
    for x in xs:
        if x.shape is None:
            cat = UNKNOWN
            continue
        for i in range(nd):
            if i == axis:
                continue
            if not dims_agree(out[i], x.shape[i]):
                raise InferError(
                    f'concat non-axis dim {i} differs across inputs: '
                    f'{out[i]} vs {x.shape[i]}')
            out[i] = merge_dim(out[i], x.shape[i])
        if known(cat) and known(x.shape[axis]):
            cat = cat + x.shape[axis]
        else:
            cat = UNKNOWN
    out[axis] = cat
    return {'Out': VarInfo(tuple(out), dtype)}


@infer_rule('split')
def _split(ctx):
    x = ctx.require('x')
    num = ctx.require_attr('num_or_sections')
    n_out = len(ctx.op.outputs.get('Out', []))
    if x.shape is None:
        return {'Out': [VarInfo(None, x.dtype)] * n_out}
    nd = len(x.shape)
    dim = ctx.attr('dim', -1)
    if not -nd <= dim < nd:
        raise InferError(f'split dim {dim} out of range for rank {nd}',
                         kind='bad-attr')
    dim %= nd
    total = x.shape[dim]
    outs = []
    if isinstance(num, int):
        if num <= 0:
            raise InferError(f'split num {num} must be > 0', kind='bad-attr')
        if known(total) and total % num != 0:
            raise InferError(
                f'split cannot divide dim {dim} of size {total} into '
                f'{num} equal parts')
        part = total // num if known(total) else UNKNOWN
        outs = [VarInfo(x.shape[:dim] + (part,) + x.shape[dim + 1:],
                        x.dtype) for _ in range(num)]
    else:
        sizes = list(num)
        free = [s for s in sizes if s in (-1, None)]
        if len(free) > 1:
            raise InferError(f'split sections {sizes} have more than one -1',
                             kind='bad-attr')
        fixed = sum(s for s in sizes if s not in (-1, None))
        for s in sizes:
            if s in (-1, None):
                part = (total - fixed) if known(total) else UNKNOWN
            else:
                part = s
            outs.append(VarInfo(x.shape[:dim] + (part,) + x.shape[dim + 1:],
                                x.dtype))
        if known(total) and not free and fixed != total:
            raise InferError(
                f'split sections {sizes} sum to {fixed}, dim {dim} has '
                f'size {total}')
    return {'Out': outs}


@infer_rule('stack')
def _stack(ctx):
    xs = ctx.inputs('xs')
    if not xs:
        raise InferError('stack needs at least one input', kind='bad-attr')
    axis = ctx.attr('axis', 0)
    base = next((x for x in xs if x.shape is not None), None)
    dtype = xs[0].dtype
    for x in xs[1:]:
        if base is not None and x.shape is not None \
                and not shapes_agree(x, base):
            raise InferError(
                f'stack inputs have incompatible shapes: {base.shape} '
                f'vs {x.shape}')
        dtype = promote(dtype, x.dtype)
    if base is None:
        return {'Out': VarInfo(None, dtype)}
    out = list(base.shape)
    out.insert(axis if axis >= 0 else axis + len(out) + 1, len(xs))
    return {'Out': VarInfo(tuple(out), dtype)}


@infer_rule('unstack')
def _unstack(ctx):
    x = ctx.require('x')
    axis = ctx.attr('axis', 0)
    n_out = len(ctx.op.outputs.get('Y', []))
    if x.shape is None:
        return {'Y': [VarInfo(None, x.dtype)] * n_out}
    out = x.shape[:axis % len(x.shape)] + x.shape[axis % len(x.shape) + 1:]
    return {'Y': [VarInfo(out, x.dtype)] * n_out}


@infer_rule('slice')
def _slice(ctx):
    x = ctx.require('x')
    axes = ctx.require_attr('axes')
    starts, ends = ctx.require_attr('starts'), ctx.require_attr('ends')
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    out = list(x.shape)
    for ax, st, en in zip(axes, starts, ends):
        d = out[ax]
        if known(d):
            lo = st if st >= 0 else max(d + st, 0)
            hi = min(en if en >= 0 else d + en, d)
            out[ax] = max(hi - min(lo, d), 0)
        else:
            out[ax] = UNKNOWN
    return {'Out': VarInfo(tuple(out), x.dtype)}


@infer_rule('flatten', 'flatten2')
def _flatten(ctx):
    x = ctx.require('x')
    axis = ctx.attr('axis', 1)
    if x.shape is None:
        return {'Out': VarInfo((UNKNOWN, UNKNOWN), x.dtype)}
    lead_dims = x.shape[:axis] if axis > 0 else ()
    tail_dims = x.shape[axis:] if axis > 0 else x.shape

    def prod(dims):
        if any(not known(d) for d in dims):
            return UNKNOWN
        return int(np.prod(dims, dtype=np.int64)) if dims else 1

    return {'Out': VarInfo((prod(lead_dims) if axis > 0 else 1,
                            prod(tail_dims)), x.dtype)}


@infer_rule('expand')
def _expand(ctx):
    x = ctx.require('x')
    times = list(ctx.require_attr('expand_times'))
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    # jnp.tile semantics: times aligned to the trailing dims
    shape = (1,) * max(len(times) - len(x.shape), 0) + x.shape
    times = [1] * max(len(shape) - len(times), 0) + times
    out = tuple(s * t if known(s) else UNKNOWN
                for s, t in zip(shape, times))
    return {'Out': VarInfo(out, x.dtype)}


@infer_rule('gather')
def _gather(ctx):
    x, idx = ctx.require('x'), ctx.require('index')
    if x.shape is None or idx.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    ishape = idx.shape
    if len(ishape) == 2 and known(ishape[1]) and ishape[1] == 1:
        ishape = ishape[:1]
    return {'Out': VarInfo(ishape + x.shape[1:], x.dtype)}


@infer_rule('one_hot')
def _one_hot(ctx):
    x = ctx.require('x')
    depth = ctx.require_attr('depth')
    if not isinstance(depth, int) or depth <= 0:
        raise InferError(f'one_hot depth {depth!r} must be a positive int',
                         kind='bad-attr')
    if x.shape is None:
        return {'Out': VarInfo(None, 'float32')}
    shape = x.shape
    if len(shape) >= 2 and known(shape[-1]) and shape[-1] == 1:
        shape = shape[:-1]
    return {'Out': VarInfo(shape + (depth,), 'float32')}


@infer_rule('lookup_table')
def _lookup_table(ctx):
    w, ids = ctx.require('w'), ctx.require('ids')
    if w.shape is not None and len(w.shape) != 2:
        raise InferError(f'lookup_table weight must be rank 2, got '
                         f'{w.display_shape()}')
    emb = w.shape[1] if w.shape is not None else UNKNOWN
    if ids.shape is None:
        return {'Out': VarInfo(None, w.dtype)}
    ishape = ids.shape
    if len(ishape) >= 2 and known(ishape[-1]) and ishape[-1] == 1:
        ishape = ishape[:-1]
    return {'Out': VarInfo(ishape + (emb,), w.dtype)}


@infer_rule('top_k')
def _top_k(ctx):
    x = ctx.require('x')
    k = ctx.require_attr('k')
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype),
                'Indices': VarInfo(None, 'int64')}
    last = x.shape[-1]
    if known(last) and isinstance(k, int) and k > last:
        raise InferError(f'top_k k={k} exceeds last dim {last}',
                         kind='bad-attr')
    out = x.shape[:-1] + (k if isinstance(k, int) else UNKNOWN,)
    return {'Out': VarInfo(out, x.dtype), 'Indices': VarInfo(out, 'int64')}


@infer_rule('arg_max', 'arg_min')
def _argminmax(ctx):
    x = ctx.require('x')
    axis = ctx.attr('axis', 0)
    from ..core.dtypes import convert_dtype
    dtype = convert_dtype(ctx.attr('dtype', 'int64'))
    if x.shape is None:
        return {'Out': VarInfo(None, dtype)}
    nd = len(x.shape)
    if not -nd <= axis < nd:
        raise InferError(f'arg_max axis {axis} out of range for rank {nd}',
                         kind='bad-attr')
    if ctx.attr('keepdims', False):
        out = tuple(1 if i == axis % nd else s
                    for i, s in enumerate(x.shape))
    else:
        out = tuple(s for i, s in enumerate(x.shape) if i != axis % nd)
    return {'Out': VarInfo(out, dtype)}


@infer_rule('where')
def _where(ctx):
    c = ctx.require('cond')
    x, y = ctx.require('x'), ctx.require('y')
    shape = broadcast_shapes(broadcast_shapes(c.shape, x.shape),
                             y.shape) \
        if None not in (c.shape, x.shape, y.shape) else None
    return {'Out': VarInfo(shape, promote(x.dtype, y.dtype))}


@infer_rule('fill_constant')
def _fill_constant(ctx):
    from ..core.dtypes import convert_dtype
    shape = ctx.require_attr('shape')
    try:
        dtype = convert_dtype(ctx.attr('dtype', 'float32'))
    except TypeError as e:
        raise InferError(str(e), kind='bad-attr')
    if 'value' not in ctx.op.attrs:
        raise InferError('fill_constant requires a value attr',
                         kind='bad-attr')
    return {'Out': VarInfo(tuple(shape), dtype)}


@infer_rule('fill_constant_batch_size_like')
def _fill_batch_like(ctx):
    from ..core.dtypes import convert_dtype
    ref = ctx.require('ref')
    shape = list(ctx.require_attr('shape'))
    dtype = convert_dtype(ctx.attr('dtype', 'float32'))
    in_idx = ctx.attr('input_dim_idx', 0)
    out_idx = ctx.attr('output_dim_idx', 0)
    shape[out_idx] = (ref.shape[in_idx]
                      if ref.shape is not None and in_idx < len(ref.shape)
                      else UNKNOWN)
    return {'Out': VarInfo(tuple(shape), dtype)}


@infer_rule('fill_any_like')
def _fill_any_like(ctx):
    from ..core.dtypes import convert_dtype
    x = ctx.require('x')
    dt = ctx.attr('dtype')
    return {'Out': VarInfo(x.shape,
                           convert_dtype(dt) if dt is not None else x.dtype)}


@infer_rule('shape')
def _shape_op(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo((len(x.shape) if x.shape is not None
                            else UNKNOWN,), 'int32')}


@infer_rule('pad')
def _pad(ctx):
    x = ctx.require('x')
    paddings = ctx.require_attr('paddings')
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    if len(paddings) != 2 * len(x.shape):
        raise InferError(
            f'pad expects {2 * len(x.shape)} padding entries for rank '
            f'{len(x.shape)}, got {len(paddings)}', kind='bad-attr')
    out = tuple(s + paddings[2 * i] + paddings[2 * i + 1] if known(s)
                else UNKNOWN for i, s in enumerate(x.shape))
    return {'Out': VarInfo(out, x.dtype)}


# ---------------------------------------------------------------------------
# rules: nn
# ---------------------------------------------------------------------------

def _conv_out_dim(in_dim, k, stride, pad_lo, pad_hi, dilation):
    if not known(in_dim):
        return UNKNOWN
    eff = (k - 1) * dilation + 1
    return (in_dim + pad_lo + pad_hi - eff) // stride + 1


def _pair(v, n=2):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * n


@infer_rule('conv2d')
def _conv2d(ctx):
    x, w = ctx.require('x'), ctx.require('weight')
    dtype = promote(x.dtype, w.dtype) if x.dtype != w.dtype else x.dtype
    if is_float(x.dtype) and is_float(w.dtype) and x.dtype != w.dtype:
        dtype = w.dtype          # _match_weight_dtype: compute in w's dtype
    if x.shape is None or w.shape is None:
        return {'Out': VarInfo(None, dtype)}
    if len(x.shape) != 4 or len(w.shape) != 4:
        raise InferError(
            f'conv2d expects rank-4 input and weight, got '
            f'x{x.display_shape()} w{w.display_shape()}')
    fmt = ctx.attr('data_format', 'NCHW')
    groups = ctx.attr('groups', 1) or 1
    n, c, h, wd = (x.shape if fmt == 'NCHW'
                   else (x.shape[0], x.shape[3], x.shape[1], x.shape[2]))
    oc, ic, kh, kw = w.shape      # weights always OIHW
    if known(c) and known(ic) and c != ic * groups:
        raise InferError(
            f'conv2d channel mismatch: input has {c} channels, weight '
            f'expects {ic} × groups={groups}')
    stride = _pair(ctx.attr('stride', 1))
    dil = _pair(ctx.attr('dilation', 1))
    padding = ctx.attr('padding', 0)
    if isinstance(padding, str):
        p = padding.upper()
        if p == 'SAME':
            oh = -(-h // stride[0]) if known(h) else UNKNOWN
            ow = -(-wd // stride[1]) if known(wd) else UNKNOWN
        elif p == 'VALID':
            oh = _conv_out_dim(h, kh, stride[0], 0, 0, dil[0]) \
                if known(kh) else UNKNOWN
            ow = _conv_out_dim(wd, kw, stride[1], 0, 0, dil[1]) \
                if known(kw) else UNKNOWN
        else:
            raise InferError(f'conv2d padding {padding!r} invalid',
                             kind='bad-attr')
    else:
        pp = _pair(padding)
        pads = ([(pp[0], pp[0]), (pp[1], pp[1])] if len(pp) == 2
                else [(pp[0], pp[1]), (pp[2], pp[3])])
        oh = _conv_out_dim(h, kh, stride[0], *pads[0], dil[0]) \
            if known(kh) else UNKNOWN
        ow = _conv_out_dim(wd, kw, stride[1], *pads[1], dil[1]) \
            if known(kw) else UNKNOWN
    if isinstance(oh, int) and oh <= 0 or isinstance(ow, int) and ow <= 0:
        raise InferError(
            f'conv2d output spatial dims are non-positive: '
            f'({oh}, {ow}) from x{x.display_shape()} w{w.display_shape()}')
    out = ((n, oc, oh, ow) if fmt == 'NCHW' else (n, oh, ow, oc))
    return {'Out': VarInfo(out, dtype)}


@infer_rule('pool2d')
def _pool2d(ctx):
    x = ctx.require('x')
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    if len(x.shape) != 4:
        raise InferError(f'pool2d expects rank-4 input, got '
                         f'{x.display_shape()}')
    fmt = ctx.attr('data_format', 'NCHW')
    n, c, h, w = (x.shape if fmt == 'NCHW'
                  else (x.shape[0], x.shape[3], x.shape[1], x.shape[2]))
    if ctx.attr('global_pooling', False) or ctx.attr('pool_size', -1) in (
            -1, (-1, -1), [-1, -1]):
        oh = ow = 1
    else:
        ks = _pair(ctx.attr('pool_size'))
        st = _pair(ctx.attr('pool_stride', 1))
        pd = _pair(ctx.attr('pool_padding', 0))
        ceil = ctx.attr('ceil_mode', False)

        def odim(d, k, s, p):
            if not known(d):
                return UNKNOWN
            num = d + 2 * p - k
            return (-(-num // s) if ceil else num // s) + 1

        oh, ow = odim(h, ks[0], st[0], pd[0]), odim(w, ks[1], st[1], pd[1])
    out = ((n, c, oh, ow) if fmt == 'NCHW' else (n, oh, ow, c))
    return {'Out': VarInfo(out, x.dtype)}


@infer_rule('adaptive_pool2d')
def _adaptive_pool2d(ctx):
    x = ctx.require('x')
    oh, ow = _pair(ctx.require_attr('pool_size'))
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    n, c = x.shape[0], x.shape[1]
    return {'Out': VarInfo((n, c, oh, ow), x.dtype)}


@infer_rule('batch_norm')
def _batch_norm(ctx):
    x = ctx.require('x')
    mean, var = ctx.require('mean'), ctx.require('variance')
    layout = ctx.attr('data_layout', 'NCHW')
    if x.shape is not None and len(x.shape) >= 2:
        c = (x.shape[1] if layout == 'NCHW' and len(x.shape) > 2
             else x.shape[-1])
        for slot, s in (('scale', ctx.input('scale')),
                        ('bias', ctx.input('bias')),
                        ('mean', mean), ('variance', var)):
            if s is not None and s.shape is not None and len(s.shape) == 1 \
                    and not dims_agree(s.shape[0], c):
                raise InferError(
                    f'batch_norm {slot} has {s.shape[0]} channels, input '
                    f'has {c}')
    return {'Y': VarInfo(x.shape, x.dtype),
            'MeanOut': VarInfo(mean.shape, mean.dtype),
            'VarianceOut': VarInfo(var.shape, var.dtype)}


@infer_rule('layer_norm', 'instance_norm', 'group_norm', 'lrn')
def _same_as_x_norm(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo(x.shape, x.dtype)}


# ---------------------------------------------------------------------------
# rules: losses / metrics
# ---------------------------------------------------------------------------

@infer_rule('softmax_with_cross_entropy')
def _softmax_ce(ctx):
    logits, label = ctx.require('logits'), ctx.require('label')
    axis = ctx.attr('axis', -1)
    soft = ctx.attr('soft_label', False)
    if logits.shape is None:
        return {'Loss': VarInfo(None, logits.dtype),
                'Softmax': VarInfo(None, logits.dtype)}
    nd = len(logits.shape)
    ax = axis % nd if -nd <= axis < nd else None
    if ax is None:
        raise InferError(f'softmax_with_cross_entropy axis {axis} out of '
                         f'range for rank {nd}', kind='bad-attr')
    if soft:
        if label.shape is not None \
                and not shapes_agree(label, logits):
            raise InferError(
                f'soft_label=True requires label shape == logits shape: '
                f'{label.display_shape()} vs {logits.display_shape()}')
        if label.dtype is not None and not is_float(label.dtype):
            raise InferError(
                f'soft_label=True requires a float label, got '
                f'{label.dtype}', kind='dtype-mismatch')
    elif label.dtype is not None and is_float(label.dtype):
        raise InferError(
            f'hard-label cross entropy requires an integer label, got '
            f'{label.dtype} (set soft_label=True for distributions)',
            kind='dtype-mismatch')
    loss_shape = tuple(1 if i == ax else s
                       for i, s in enumerate(logits.shape))
    return {'Loss': VarInfo(loss_shape, logits.dtype),
            'Softmax': VarInfo(logits.shape, logits.dtype)}


@infer_rule('cross_entropy')
def _cross_entropy(ctx):
    x = ctx.require('x')
    if x.shape is None:
        return {'Out': VarInfo(None, x.dtype)}
    return {'Out': VarInfo(x.shape[:-1] + (1,), x.dtype)}


@infer_rule('square_error_cost')
def _square_error(ctx):
    # the kernel computes jnp broadcast x - label, so the rule broadcasts
    # too (stricter-than-kernel rules would reject working programs)
    x, y = ctx.require('x'), ctx.require('label')
    shape = (broadcast_shapes(x.shape, y.shape, 'input/label')
             if x.shape is not None and y.shape is not None else None)
    return {'Out': VarInfo(shape, promote(x.dtype, y.dtype))}


@infer_rule('sigmoid_cross_entropy_with_logits')
def _sigmoid_ce(ctx):
    x = ctx.require('x')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('accuracy')
def _accuracy(ctx):
    return {'Out': VarInfo((), 'float32'),
            'Correct': VarInfo((), 'int64'),
            'Total': VarInfo((), 'int64')}


# ---------------------------------------------------------------------------
# rules: optimizer updates (outputs mirror their state inputs)
# ---------------------------------------------------------------------------

# op type → {output slot: input slot whose info it mirrors}
_OPT_MIRROR = {
    'sgd': {'ParamOut': 'param'},
    'momentum': {'ParamOut': 'param', 'VelocityOut': 'velocity'},
    'lars_momentum': {'ParamOut': 'param', 'VelocityOut': 'velocity'},
    'adam': {'ParamOut': 'param', 'Moment1Out': 'moment1',
             'Moment2Out': 'moment2', 'Beta1PowOut': 'beta1_pow',
             'Beta2PowOut': 'beta2_pow'},
    'adamax': {'ParamOut': 'param', 'MomentOut': 'moment',
               'InfNormOut': 'inf_norm', 'Beta1PowOut': 'beta1_pow'},
    'adagrad': {'ParamOut': 'param', 'MomentOut': 'moment'},
    'decayed_adagrad': {'ParamOut': 'param', 'MomentOut': 'moment'},
    'adadelta': {'ParamOut': 'param', 'AvgSquaredGradOut': 'avg_squared_grad',
                 'AvgSquaredUpdateOut': 'avg_squared_update'},
    'rmsprop': {'ParamOut': 'param', 'MomentOut': 'moment',
                'MeanSquareOut': 'mean_square', 'MeanGradOut': 'mean_grad'},
    'ftrl': {'ParamOut': 'param', 'SquaredAccumOut': 'squared_accum',
             'LinearAccumOut': 'linear_accum'},
    'lamb': {'ParamOut': 'param', 'Moment1Out': 'moment1',
             'Moment2Out': 'moment2', 'Beta1PowOut': 'beta1_pow',
             'Beta2PowOut': 'beta2_pow'},
    'dpsgd': {'ParamOut': 'param'},
}


def _opt_rule(ctx):
    mirror = _OPT_MIRROR[ctx.op.type]
    param = ctx.input('param')
    grad = ctx.input('grad')
    if param is not None and grad is not None \
            and not shapes_agree(param, grad):
        raise InferError(
            f'{ctx.op.type} param/grad shapes differ: '
            f'{param.display_shape()} vs {grad.display_shape()}')
    out = {}
    for out_slot, in_slot in mirror.items():
        src = ctx.input(in_slot)
        if src is not None:
            out[out_slot] = VarInfo(src.shape, src.dtype)
    return out


for _t in _OPT_MIRROR:
    infer_rule(_t)(_opt_rule)


# rows-only (padded-COO) update ops — docs/SPARSE.md. rows is rank-1
# int, vals rank-2 with the param's embedding width; outputs mirror the
# param/slot inputs exactly like the dense family above.
_SPARSE_OPT_MIRROR = {
    'sparse_sgd': {'ParamOut': 'param'},
    'sparse_momentum': {'ParamOut': 'param', 'VelocityOut': 'velocity'},
    'sparse_adagrad': {'ParamOut': 'param', 'MomentOut': 'moment'},
    'sparse_adam': {'ParamOut': 'param', 'Moment1Out': 'moment1',
                    'Moment2Out': 'moment2', 'Beta1PowOut': 'beta1_pow',
                    'Beta2PowOut': 'beta2_pow'},
}


def _sparse_opt_rule(ctx):
    mirror = _SPARSE_OPT_MIRROR[ctx.op.type]
    param = ctx.input('param')
    rows, vals = ctx.input('rows'), ctx.input('vals')
    if rows is not None and rows.shape is not None and len(rows.shape) != 1:
        raise InferError(
            f'{ctx.op.type} rows must be rank 1 (padded COO row ids), got '
            f'{rows.display_shape()}')
    if vals is not None and vals.shape is not None and len(vals.shape) != 2:
        raise InferError(
            f'{ctx.op.type} vals must be rank 2 (rows × embedding dim), '
            f'got {vals.display_shape()}')
    if rows is not None and vals is not None \
            and rows.shape is not None and vals.shape is not None \
            and known(rows.shape[0]) and known(vals.shape[0]) \
            and rows.shape[0] != vals.shape[0]:
        raise InferError(
            f'{ctx.op.type} rows/vals leading dims differ: '
            f'{rows.display_shape()} vs {vals.display_shape()}')
    if param is not None and vals is not None \
            and param.shape is not None and vals.shape is not None \
            and len(param.shape) == 2 \
            and known(param.shape[1]) and known(vals.shape[1]) \
            and param.shape[1] != vals.shape[1]:
        raise InferError(
            f'{ctx.op.type} vals width {vals.shape[1]} does not match '
            f'table width {param.shape[1]}')
    if param is not None and vals is not None \
            and param.dtype is not None and vals.dtype is not None \
            and param.dtype != vals.dtype:
        raise InferError(
            f'{ctx.op.type} param dtype {param.dtype} vs vals dtype '
            f'{vals.dtype}', kind='dtype-mismatch')
    out = {}
    for out_slot, in_slot in mirror.items():
        src = ctx.input(in_slot)
        if src is not None:
            out[out_slot] = VarInfo(src.shape, src.dtype)
    return out


for _t in _SPARSE_OPT_MIRROR:
    infer_rule(_t)(_sparse_opt_rule)


_FUSED_OPT_MIRROR = {
    'fused_sgd': {'ParamOut': 'params'},
    'fused_momentum': {'ParamOut': 'params', 'VelocityOut': 'velocities'},
    'fused_lars_momentum': {'ParamOut': 'params',
                            'VelocityOut': 'velocities'},
    'fused_adam': {'ParamOut': 'params', 'Moment1Out': 'moment1s',
                   'Moment2Out': 'moment2s'},
}


def _fused_opt_rule(ctx):
    mirror = _FUSED_OPT_MIRROR[ctx.op.type]
    params = ctx.inputs('params')
    grads = ctx.inputs('grads')
    if len(params) != len(grads):
        raise InferError(
            f'{ctx.op.type} has {len(params)} params but {len(grads)} '
            f'grads', kind='bad-attr')
    dtypes = {p.dtype for p in params + grads if p.dtype is not None}
    if len(dtypes) > 1:
        raise InferError(
            f'{ctx.op.type} bundle mixes dtypes {sorted(dtypes)}; the '
            f'flattened multi-tensor update requires one dtype',
            kind='dtype-mismatch')
    for p, g in zip(params, grads):
        if not shapes_agree(p, g):
            raise InferError(
                f'{ctx.op.type} param/grad shapes differ: '
                f'{p.display_shape()} vs {g.display_shape()}')
    out = {}
    for out_slot, in_slot in mirror.items():
        srcs = ctx.inputs(in_slot)
        out[out_slot] = [VarInfo(s.shape, s.dtype) for s in srcs]
    if ctx.op.type == 'fused_adam':
        n = len(params)
        out['Beta1PowOut'] = [VarInfo((1,), 'float32')] * n
        out['Beta2PowOut'] = [VarInfo((1,), 'float32')] * n
    return out


for _t in _FUSED_OPT_MIRROR:
    infer_rule(_t)(_fused_opt_rule)


# ---------------------------------------------------------------------------
# rules: collectives
# ---------------------------------------------------------------------------

_COMM_DTYPES = (None, 'f32', 'bf16', 'int8')


def _check_comm_dtype(ctx):
    cd = ctx.attr('comm_dtype')
    if cd not in _COMM_DTYPES:
        raise InferError(
            f'comm_dtype {cd!r} invalid; expected one of '
            f'{[d for d in _COMM_DTYPES if d]}', kind='bad-attr')


@infer_rule('c_allreduce_sum', 'c_allreduce_max', 'c_allreduce_min',
            'c_allreduce_prod')
def _allreduce(ctx):
    _check_comm_dtype(ctx)
    x = ctx.require('x')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('c_allreduce_sum_bucket')
def _allreduce_bucket(ctx):
    _check_comm_dtype(ctx)
    xs = ctx.inputs('xs')
    if len(ctx.op.outputs.get('Out', [])) != len(xs):
        raise InferError(
            f'c_allreduce_sum_bucket has {len(xs)} inputs but '
            f'{len(ctx.op.outputs.get("Out", []))} outputs',
            kind='bad-attr')
    dtypes = {x.dtype for x in xs if x.dtype is not None}
    if len(dtypes) > 1:
        raise InferError(
            f'c_allreduce_sum_bucket mixes operand dtypes '
            f'{sorted(dtypes)}; buckets must be dtype-uniform',
            kind='dtype-mismatch')
    return {'Out': [VarInfo(x.shape, x.dtype) for x in xs]}


# ---------------------------------------------------------------------------
# rules: paged KV-cache attention (serving/decode)
# ---------------------------------------------------------------------------

def _holds(width, values):
    return not (known(width) and known(values)) or width >= values


def _check_kv_scales(ctx):
    """The optional int8-pool dequant scales: one f32 per (block, position,
    head) row — rank 3 (num_blocks, block_size, H) beside pages
    (num_blocks, block_size, W ≥ H·D): the same blocks, q's H heads, and
    rows wide enough for them, when known. Typed here so the generic byte
    model prices a quantized pool as 1 B/elem payload + 4 B/row scales with
    no op-specific bytes rule."""
    q = ctx.input('q')
    heads = head_dim = UNKNOWN
    if q is not None and q.shape is not None and len(q.shape) in (3, 4):
        heads, head_dim = q.shape[1], q.shape[-1]
    pages = ctx.input('k_pages')
    for slot in ('k_scales', 'v_scales'):
        sc = ctx.input(slot)
        if sc is None:
            continue
        if sc.dtype is not None and sc.dtype != 'float32':
            raise InferError(
                f'{slot} must be float32 row scales, got {sc.dtype}',
                kind='dtype-mismatch')
        if sc.shape is not None:
            if len(sc.shape) != 3:
                raise InferError(
                    f'{slot} expects rank 3 (num_blocks, block_size, H), '
                    f'got rank {len(sc.shape)}')
            if not dims_agree(sc.shape[2], heads):
                raise InferError(
                    f'{slot} shape {tuple(sc.shape)} holds a scale for '
                    f'{sc.shape[2]} heads, q has {heads}')
            if (pages is not None and pages.shape is not None
                    and len(pages.shape) == 3
                    and not (dims_agree(sc.shape[0], pages.shape[0])
                             and dims_agree(sc.shape[1], pages.shape[1])
                             and _holds(pages.shape[2],
                                        _mul_dims(heads, head_dim)))):
                raise InferError(
                    f'{slot} shape {tuple(sc.shape)} does not match the '
                    f'pages\' (num_blocks, block_size, W >= H·D) '
                    f'{tuple(pages.shape)}')


@infer_rule('paged_attention')
def _paged_attention(ctx):
    # decode read: q (S, H, D) -> (S, H, D); multi-query speculative
    # verify: q (S, H, K, D) -> (S, H, K, D). Out always mirrors q.
    q = ctx.require('q')
    if q.shape is not None and len(q.shape) not in (3, 4):
        raise InferError(
            f'paged_attention expects q of rank 3 (decode) or 4 '
            f'(multi-query verify), got rank {len(q.shape)}')
    _check_kv_scales(ctx)
    if ctx.attr('block_window', False):
        # a window model's block read: (S, H, K, D) over rows of
        # kv_heads <= H heads, every row at the same extent
        if q.shape is not None and len(q.shape) != 4:
            raise InferError('paged_attention block_window expects q of '
                             f'rank 4 (S, H, K, D), got rank {len(q.shape)}')
        if ctx.input('k_scales') is not None:
            raise InferError('paged_attention block_window has no int8 '
                             'form: it takes no row scales')
        _grouped_heads('paged_attention', _dim(q, 1),
                       ctx.attr('kv_heads', None))
    elif ctx.attr('kv_heads', None) is not None:
        # the grouped single-query read, and with span its sliding form
        if q.shape is not None and len(q.shape) != 3:
            raise InferError('paged_attention kv_heads without block_window '
                             'expects q of rank 3 (S, H, D), got rank '
                             f'{len(q.shape)}')
        if ctx.input('k_scales') is not None:
            raise InferError('paged_attention kv_heads has no int8 form: '
                             'it takes no row scales')
        _grouped_heads('paged_attention', _dim(q, 1), ctx.attr('kv_heads'))
    _span_attr(ctx, 'paged_attention')
    return {'Out': VarInfo(q.shape, q.dtype)}


def _span_attr(ctx, what):
    span = int(ctx.attr('span', 0))
    if span < 0:
        raise InferError(f'{what} span={span} is negative', kind='bad-attr')
    if span and ctx.attr('kv_heads', None) is None:
        raise InferError(f'{what} span={span} needs kv_heads: a sliding '
                         f'layer is read in the grouped form',
                         kind='bad-attr')


def _grouped_heads(what, heads, kv_heads):
    if kv_heads is not None and known(heads) and (
            int(kv_heads) < 1 or heads % int(kv_heads)):
        raise InferError(f'{what}: {heads} query heads do not divide over '
                         f'kv_heads={kv_heads}', kind='bad-attr')


@infer_rule('paged_prefill_attention')
def _paged_prefill_attention(ctx):
    q = ctx.require('q')
    if q.shape is not None and len(q.shape) != 4:
        raise InferError(
            f'paged_prefill_attention expects q of rank 4 (1, H, L, D), '
            f'got rank {len(q.shape)}')
    _check_kv_scales(ctx)
    block_len = int(ctx.attr('block_len', 0))
    if block_len < 0:
        raise InferError(f'paged_prefill_attention block_len={block_len} is '
                         f'negative', kind='bad-attr')
    k = ctx.input('k')
    if block_len and k is not None and k.shape is not None \
            and len(k.shape) == 4:
        # the block mask takes grouped heads: k, v (1, G, L, D)
        _grouped_heads('paged_prefill_attention', _dim(q, 1), _dim(k, 1)
                       if known(_dim(k, 1)) else None)
    kv_heads = ctx.attr('kv_heads', None)
    if kv_heads is not None:
        # the causal grouped form: k, v (1, G, L, D)
        if block_len:
            raise InferError('paged_prefill_attention takes block_len or '
                             'kv_heads, not both', kind='bad-attr')
        _grouped_heads('paged_prefill_attention', _dim(q, 1), kv_heads)
        if k is not None and k.shape is not None and len(k.shape) == 4 \
                and not dims_agree(_dim(k, 1), int(kv_heads)):
            raise InferError(f'paged_prefill_attention kv_heads={kv_heads} '
                             f'but k holds {_dim(k, 1)} heads')
    _span_attr(ctx, 'paged_prefill_attention')
    return {'Out': VarInfo(q.shape, q.dtype)}


# ---------------------------------------------------------------------------
# rules: the modern decoder block (ops/llm_ops.py)
# ---------------------------------------------------------------------------

def _dim(info, i):
    """Dim ``i`` (negative from the end) of a VarInfo, UNKNOWN if the rank
    is."""
    if info is None or info.shape is None:
        return UNKNOWN
    return info.shape[i]


def _mul_dims(a, b):
    return a * b if known(a) and known(b) else UNKNOWN


def _contracts(what, x_dim, w_dim):
    if not dims_agree(x_dim, w_dim):
        raise InferError(f'{what}: contraction dims differ: {x_dim} vs '
                         f'{w_dim}')


@infer_rule('rms_norm')
def _rms_norm(ctx):
    x = ctx.require('x')
    _contracts('rms_norm x against scale', _dim(x, -1),
               _dim(ctx.require('scale'), -1))
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('rope')
def _rope(ctx):
    x = ctx.require('x')
    if x.shape is not None and len(x.shape) not in (3, 4):
        raise InferError(f'rope expects x of rank 3 (B, S, D) or 4 '
                         f'(B, S, H, D), got rank {len(x.shape)}')
    rotated = _dim(x, -1)
    if known(rotated) and (rotated - int(ctx.attr('nope_dim', 0))) % 2:
        raise InferError(
            f'rope turns pairs of lanes: {rotated} lanes less nope_dim='
            f'{ctx.attr("nope_dim", 0)} is odd', kind='bad-attr')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('lm_head')
def _lm_head(ctx):
    x, w = ctx.require('x'), ctx.require('w')
    hidden, vocab = (1, 0) if ctx.attr('tied', False) else (0, 1)
    _contracts('lm_head', _dim(x, -1), _dim(w, hidden))
    shape = None if x.shape is None \
        else tuple(x.shape[:-1]) + (_dim(w, vocab),)
    return {'Out': VarInfo(shape, 'float32')}


@infer_rule('diffusion_pick')
def _diffusion_pick(ctx):
    rows = ctx.require('rows')
    if rows.shape is not None and len(rows.shape) < 1:
        raise InferError('diffusion_pick expects rows of rank >= 1 (..., V)')
    vocab, mask = _dim(rows, -1), int(ctx.attr('mask_token_id', -1))
    if known(vocab) and mask >= vocab:
        raise InferError(f'diffusion_pick mask_token_id={mask} is not a '
                         f'column of {vocab}', kind='bad-attr')
    shape = None if rows.shape is None else tuple(rows.shape[:-1])
    return {'Ids': VarInfo(shape, 'int32'),
            'Confidence': VarInfo(shape, 'float32')}


@infer_rule('swiglu_ffn')
def _swiglu_ffn(ctx):
    x = ctx.require('x')
    for slot in ('w_gate', 'w_up'):
        _contracts(f'swiglu_ffn x against {slot}', _dim(x, -1),
                   _dim(ctx.require(slot), 0))
    _contracts('swiglu_ffn w_down against x', _dim(ctx.require('w_down'), 1),
               _dim(x, -1))
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('relu2_ffn')
def _relu2_ffn(ctx):
    x = ctx.require('x')
    _contracts('relu2_ffn x against w_up', _dim(x, -1),
               _dim(ctx.require('w_up'), 0))
    _contracts('relu2_ffn w_down against x', _dim(ctx.require('w_down'), 1),
               _dim(x, -1))
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('sigmoid_gate')
def _sigmoid_gate(ctx):
    x, gate = ctx.require('x'), ctx.require('gate')
    if x.shape is not None and gate.shape is not None and (
            len(x.shape) != len(gate.shape) or not all(
                dims_agree(a, b) for a, b in zip(x.shape, gate.shape))):
        raise InferError(f'sigmoid_gate: x {tuple(x.shape)} and gate '
                         f'{tuple(gate.shape)} differ in shape')
    return {'Out': VarInfo(x.shape, x.dtype)}


@infer_rule('moe_router')
def _moe_router(ctx):
    x, w = ctx.require('x'), ctx.require('w_gate')
    _contracts('moe_router', _dim(x, -1), _dim(w, 0))
    bias = ctx.input('bias')
    if bias is not None:
        _contracts('moe_router bias against experts', _dim(bias, 0),
                   _dim(w, 1))
    scoring = ctx.attr('scoring_func', 'sigmoid')
    if scoring not in ('sigmoid', 'softmax'):
        raise InferError(f'moe_router scoring_func={scoring!r} is neither '
                         f"'sigmoid' nor 'softmax'", kind='bad-attr')
    k = int(ctx.require_attr('top_k'))
    if known(_dim(w, 1)) and not 0 < k <= _dim(w, 1):
        raise InferError(f'moe_router top_k={k} of {_dim(w, 1)} experts',
                         kind='bad-attr')
    return {'Ids': VarInfo((_dim(x, 0), k), 'int32'),
            'Weights': VarInfo((_dim(x, 0), k), 'float32')}


@infer_rule('moe_experts')
def _moe_experts(ctx):
    x, up = ctx.require('x'), ctx.require('w_up')
    gate = ctx.input('w_gate')
    activation = ctx.attr('activation', 'silu')
    if (activation == 'silu') != (gate is not None) \
            or activation not in ('silu', 'relu2'):
        raise InferError(f'moe_experts activation={activation!r}: \'silu\' '
                         f'takes w_gate, \'relu2\' none', kind='bad-attr')
    first = up if gate is None else gate
    if first.shape is not None and len(first.shape) != 3:
        raise InferError(f'moe_experts expects w_up of rank 3 (E, h, f), '
                         f'got rank {len(first.shape)}')
    _contracts('moe_experts x against w_up', _dim(x, -1), _dim(first, 1))
    _contracts('moe_experts w_down against x', _dim(ctx.require('w_down'), 2),
               _dim(x, -1))
    held = ctx.attr('experts_held', None)
    if held is not None:
        first_held, count = (int(n) for n in held)
        if first_held < 0 or count < 1 \
                or not dims_agree(_dim(first, 0), count):
            raise InferError(
                f'moe_experts experts_held={tuple(held)!r}: the weights are '
                f'of {_dim(first, 0)} experts', kind='bad-attr')
    return {'Out': VarInfo(x.shape, x.dtype),
            'Counts': VarInfo((_dim(first, 0),), 'int32')}


def _mla_out(ctx, what):
    """(tokens..., H · v_dim) of a latent attention op, q (.., .., H, D)."""
    q, w = ctx.require('q'), ctx.require('w_kvb')
    if q.shape is not None and len(q.shape) != 4:
        raise InferError(f'{what} expects q of rank 4, got rank '
                         f'{len(q.shape)}')
    nope, v_dim = (int(ctx.require_attr('qk_nope_dim')),
                   int(ctx.require_attr('v_dim')))
    heads = _dim(q, 2)
    _contracts(f'{what} w_kvb against heads x (nope + v)', _dim(w, 1),
               _mul_dims(heads, nope + v_dim))
    return {'Out': VarInfo((_dim(q, 0), _dim(q, 1),
                            _mul_dims(heads, v_dim)), q.dtype)}


@infer_rule('mla_prefill_attention')
def _mla_prefill_attention(ctx):
    rank, width = _dim(ctx.require('w_kvb'), 0), _dim(ctx.require('latent'),
                                                      -1)
    if known(rank) and known(width) and width <= rank:
        raise InferError(f'mla_prefill_attention: latent rows of {width} '
                         f'hold no rotary part beside rank {rank}')
    return _mla_out(ctx, 'mla_prefill_attention')


@infer_rule('mla_decode_attention')
def _mla_decode_attention(ctx):
    pages = ctx.require('pages')
    if pages.shape is not None and len(pages.shape) != 3:
        raise InferError(
            f'mla_decode_attention expects pages of rank 3 (blocks, block, '
            f'row width), got rank {len(pages.shape)}')
    return _mla_out(ctx, 'mla_decode_attention')


@infer_rule('retention_gate')
def _retention_gate(ctx):
    x, w = ctx.require('x'), ctx.require('w')
    _contracts('retention_gate', _dim(x, -1), _dim(w, 0))
    shape = None if x.shape is None else tuple(x.shape[:-1]) + (_dim(w, 1),)
    return {'Out': VarInfo(shape, 'float32')}


def _retention_heads(ctx, what):
    """(tokens.., H·d) of a power-retention op and its (G, P, d) state
    block: q (.., .., H, d) over k, v (.., .., G, d), H a multiple of G."""
    q, k, v = ctx.require('q'), ctx.require('k'), ctx.require('v')
    for name, info in (('q', q), ('k', k), ('v', v)):
        if info.shape is not None and len(info.shape) != 4:
            raise InferError(f'{what} expects {name} of rank 4, got rank '
                             f'{len(info.shape)}')
    heads, groups, d = _dim(q, 2), _dim(k, 2), _dim(q, 3)
    if not (dims_agree(groups, _dim(v, 2)) and dims_agree(d, _dim(k, 3))
            and dims_agree(d, _dim(v, 3))):
        raise InferError(f'{what}: q, k and v disagree on heads or head '
                         f'size: {q.shape}, {k.shape}, {v.shape}')
    if known(heads) and known(groups) and (groups < 1 or heads % groups):
        raise InferError(f'{what}: {heads} query heads do not divide over '
                         f'{groups} key/value heads')
    _contracts(f'{what} log_gate against key/value heads',
               _dim(ctx.require('log_gate'), 2), groups)
    block = None
    if known(d):
        from ..ops.llm_ops import retention_state_rows
        if d % 2:
            raise InferError(f'{what}: head size {d} is odd')
        block = (groups, retention_state_rows(d)[2], d)
    return (VarInfo((_dim(q, 0), _dim(q, 1), _mul_dims(heads, d)), q.dtype),
            block)


@infer_rule('power_retention_prefill')
def _power_retention_prefill(ctx):
    out, block = _retention_heads(ctx, 'power_retention_prefill')
    if int(ctx.attr('chunk', 256)) < 1:
        raise InferError('power_retention_prefill: chunk must be positive',
                         kind='bad-attr')
    state = None if block is None else (_dim(ctx.require('q'), 0),) + block
    return {'Out': out, 'State': VarInfo(state, 'float32')}


@infer_rule('power_retention_step')
def _power_retention_step(ctx):
    out, block = _retention_heads(ctx, 'power_retention_step')
    state = ctx.require('state')
    if state.shape is not None and block is not None and not (
            len(state.shape) == 4 and all(
                dims_agree(a, b) for a, b in zip(state.shape[1:], block))):
        raise InferError(
            f'power_retention_step: state rows of {state.shape[1:]} are '
            f'not the block {block} of these heads')
    _contracts('power_retention_step rows against slots',
               _dim(ctx.require('rows'), 0), _dim(ctx.require('q'), 0))
    return {'Out': out, 'State': VarInfo(state.shape, 'float32')}


def _short_conv(ctx, what):
    """((.., T, h) of a gated short convolution, its (1, L - 1, h) state
    block): x (.., T, 3h) rows [B | C | z] under w (L, h) taps."""
    x, w = ctx.require('x'), ctx.require('w')
    if x.shape is not None and len(x.shape) != 3:
        raise InferError(f'{what} expects x of rank 3 (.., T, 3h), got rank '
                         f'{len(x.shape)}')
    if w.shape is not None and len(w.shape) != 2:
        raise InferError(f'{what} expects w of rank 2 (L, h), got rank '
                         f'{len(w.shape)}')
    h, taps = _dim(w, 1), _dim(w, 0)
    _contracts(f'{what} rows [B | C | z] against 3 x the taps\' width',
               _dim(x, -1), _mul_dims(h, 3))
    if known(taps) and taps < 2:
        raise InferError(f'{what}: a filter of {taps} tap(s) keeps no state')
    block = (1, taps - 1, h) if known(taps) else None
    return VarInfo((_dim(x, 0), _dim(x, 1), h), x.dtype), block


@infer_rule('short_conv_prefill')
def _short_conv_prefill(ctx):
    out, block = _short_conv(ctx, 'short_conv_prefill')
    state = None if block is None else (_dim(ctx.require('x'), 0),) + block
    return {'Out': out, 'State': VarInfo(state, 'float32')}


@infer_rule('short_conv_step')
def _short_conv_step(ctx):
    out, block = _short_conv(ctx, 'short_conv_step')
    state = ctx.require('state')
    if state.shape is not None and block is not None and not (
            len(state.shape) == 4 and all(
                dims_agree(a, b) for a, b in zip(state.shape[1:], block))):
        raise InferError(
            f'short_conv_step: state rows of {state.shape[1:]} are not the '
            f'block {block} of these taps')
    _contracts('short_conv_step rows against slots',
               _dim(ctx.require('rows'), 0), _dim(ctx.require('x'), 0))
    return {'Out': out, 'State': VarInfo(state.shape, 'float32')}


def _ssm(ctx, what):
    """((.., T, H·P) of a Mamba-2 mixer, its (P·N + W, H) state block): x
    (.., T, h) under W_in (h, 2·H·P + 2·G·N + H), taps (K, conv_dim)."""
    x, w_in, conv = ctx.require('x'), ctx.require('w_in'), ctx.require(
        'conv_w')
    if x.shape is not None and len(x.shape) != 3:
        raise InferError(f'{what} expects x of rank 3 (.., T, h), got rank '
                         f'{len(x.shape)}')
    _contracts(f'{what} x against w_in', _dim(x, -1), _dim(w_in, 0))
    heads, p = _dim(ctx.require('dt_bias'), 0), int(ctx.require_attr(
        'head_dim'))
    groups = int(ctx.require_attr('groups'))
    conv_dim, taps = _dim(conv, 1), _dim(conv, 0)
    block = None
    if known(heads) and known(conv_dim) and known(taps):
        d_inner = heads * p
        n = (conv_dim - d_inner) // (2 * groups) if groups > 0 else 0
        if n < 1 or d_inner + 2 * groups * n != conv_dim \
                or heads % max(groups, 1):
            raise InferError(f'{what}: a filter over {conv_dim} channels is '
                             f'no x of {heads} heads of {p} and B, C of '
                             f'{groups} groups', kind='bad-attr')
        _contracts(f'{what} w_in against z | xBC | dt', _dim(w_in, 1),
                   d_inner + conv_dim + heads)
        if taps < 2:
            raise InferError(f'{what}: a filter of {taps} tap(s) keeps no '
                             f'state')
        from ..ops.llm_ops import ssm_state_width
        block = (sum(ssm_state_width(heads, p, n, conv_dim, taps)), heads)
    out = VarInfo((_dim(x, 0), _dim(x, 1), _mul_dims(heads, p)), x.dtype)
    return out, block


@infer_rule('ssm_scan_prefill')
def _ssm_scan_prefill(ctx):
    out, block = _ssm(ctx, 'ssm_scan_prefill')
    if int(ctx.attr('chunk', 128)) < 1:
        raise InferError('ssm_scan_prefill: chunk must be positive',
                         kind='bad-attr')
    state = None if block is None else (_dim(ctx.require('x'), 0),) + block
    return {'Out': out, 'State': VarInfo(state, 'float32')}


@infer_rule('ssm_scan_step')
def _ssm_scan_step(ctx):
    out, block = _ssm(ctx, 'ssm_scan_step')
    state = ctx.require('state')
    if state.shape is not None and block is not None and not (
            len(state.shape) == 3 and all(
                dims_agree(a, b) for a, b in zip(state.shape[1:], block))):
        raise InferError(
            f'ssm_scan_step: state rows of {state.shape[1:]} are not the '
            f'block {block} of these heads')
    _contracts('ssm_scan_step rows against slots',
               _dim(ctx.require('rows'), 0), _dim(ctx.require('x'), 0))
    return {'Out': out, 'State': VarInfo(state.shape, 'float32')}


# ---------------------------------------------------------------------------
# rules: framework-internal ops
# ---------------------------------------------------------------------------

@infer_rule('__constant__')
def _ir_constant(ctx):
    from ..core.dtypes import convert_dtype
    v = np.asarray(ctx.require_attr('value'))
    return {'Out': VarInfo(v.shape, convert_dtype(v.dtype))}


@infer_rule('__init__')
def _ir_init(ctx):
    from ..core.dtypes import convert_dtype
    return {'Out': VarInfo(tuple(ctx.require_attr('shape')),
                           convert_dtype(ctx.attr('dtype', 'float32')))}
