"""Static per-op FLOP/byte cost model over the VarInfo lattice — zero tracing.

The verifier (infer.py) proves shapes and dtypes for every op the tier-1
recipes emit; this module multiplies those facts into costs *before* XLA
does: a :func:`cost_rule` registry (same shape as ``@infer_rule``) maps op
types to FLOP estimates, and byte traffic falls out of the VarInfos
generically (Σ input bytes read + Σ output bytes written). plan.py folds
the per-op costs into a whole-Program liveness/peak-HBM plan.

Conventions (docs/ANALYSIS.md "Cost model"):

- **Byte widths are RUNTIME widths**, not declared widths: ``int64``
  computes as int32 on device under the default x64-off config
  (core/dtypes.to_jax_dtype), so it costs 4 bytes/elem here too. That is
  what makes the plan's accounting comparable to the executor's measured
  fetch/feed/state byte counters.
- **FLOPs are multiply-add-counted estimates**, not exact instruction
  counts: matmul = 2·M·K·N, conv2d = 2·out·(C_in·kh·kw), elementwise =
  out elems, transcendentals = ``TRANSCENDENTAL_FLOPS``·elems, optimizer
  updates = a per-op factor·param elems (``_OPT_FLOP_FACTORS``). Pure
  data-movement ops (reshape/transpose/concat/…) are 0 FLOPs — their
  cost is the bytes the generic accounting already charges.
- **UNKNOWN dims** (dynamic batch) substitute ``assume_dim`` (callers
  pass the real feed batch when they have one — the executor's plan hook
  does), so a plan over a concrete feed signature is exact.

Coverage contract: every op type with an inference rule has a cost rule
(asserted in tier-1), so anything the 6 verifier recipes emit — pre- or
post-pass-pipeline, ``fused_*`` bundles and collective buckets included
— is costed. Ops without a rule fall back to bytes-only (0 FLOPs) and
are reported by plan.py as coverage gaps, never errors.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import infer
from .infer import UNKNOWN, VarInfo, declared_info, known

__all__ = ['OpCost', 'cost_rule', 'has_cost_rule', 'all_cost_rules',
           'dtype_nbytes', 'info_nbytes', 'op_cost', 'CostCtx',
           'TRANSCENDENTAL_FLOPS']

# device (runtime) byte width per canonical dtype name; int64 maps to 4
# because the executor computes it as int32 (to_jax_dtype, x64 off)
_DTYPE_NBYTES = {
    'bool': 1, 'int8': 1, 'uint8': 1, 'int16': 2, 'int64': 4, 'int32': 4,
    'float16': 2, 'bfloat16': 2, 'float32': 4, 'float64': 8,
    'complex64': 8,
}

# cost of one exp/log/tanh-class element relative to one add/mul
TRANSCENDENTAL_FLOPS = 8


def dtype_nbytes(dtype: Optional[str]) -> int:
    """Runtime bytes per element; unknown dtype prices as float32."""
    return _DTYPE_NBYTES.get(dtype, 4)


def info_elems(info: Optional[VarInfo], assume_dim: int = 1) -> int:
    """Element count with UNKNOWN dims priced at `assume_dim`. Rank-unknown
    infos price as one element (a scalar) — coverage gap, never a crash."""
    if info is None or info.shape is None:
        return 1
    n = 1
    for s in info.shape:
        n *= int(s) if known(s) else int(assume_dim)
    return int(n)


def info_nbytes(info: Optional[VarInfo], assume_dim: int = 1) -> int:
    if info is None:
        return 0
    return info_elems(info, assume_dim) * dtype_nbytes(info.dtype)


class OpCost:
    """Cost of one op: FLOPs plus bytes read/written (HBM traffic)."""

    __slots__ = ('flops', 'bytes_in', 'bytes_out')

    def __init__(self, flops=0, bytes_in=0, bytes_out=0):
        self.flops = int(flops)
        self.bytes_in = int(bytes_in)
        self.bytes_out = int(bytes_out)

    @property
    def bytes(self):
        return self.bytes_in + self.bytes_out

    @property
    def flops_per_byte(self):
        """Arithmetic intensity — the remat selector's ranking key."""
        return self.flops / self.bytes if self.bytes else 0.0

    def __repr__(self):
        return (f'OpCost(flops={self.flops}, bytes_in={self.bytes_in}, '
                f'bytes_out={self.bytes_out})')


# ---------------------------------------------------------------------------
# rule registry (one FLOP estimator per op type; bytes are generic)
# ---------------------------------------------------------------------------

_COST_RULES: Dict[str, object] = {}


def cost_rule(*op_types):
    """Decorator: register a FLOP rule for the given op types. The rule
    receives a :class:`CostCtx` and returns the op's FLOP count."""

    def deco(fn):
        for t in op_types:
            if t in _COST_RULES:
                raise ValueError(f'cost rule for {t!r} registered twice')
            _COST_RULES[t] = fn
        return fn

    return deco


def has_cost_rule(op_type: str) -> bool:
    return op_type in _COST_RULES


def all_cost_rules():
    return dict(_COST_RULES)


class CostCtx:
    """What a cost rule may consult: input/output VarInfos resolved through
    the flow env (which plan.py keeps infer-bound as it walks), the op's
    attrs, and element-count helpers under the `assume_dim` substitution."""

    def __init__(self, op, env: Dict[str, VarInfo], block, assume_dim=1):
        self.op = op
        self.env = env
        self.block = block
        self.assume_dim = int(assume_dim)

    def info_of(self, name: str) -> VarInfo:
        if name in self.env:
            return self.env[name]
        if self.block is not None and self.block.has_var(name):
            return declared_info(self.block.var(name))
        return VarInfo()

    def input(self, slot: str) -> Optional[VarInfo]:
        names = self.op.inputs.get(slot, [])
        return self.info_of(names[0]) if names else None

    def inputs(self, slot: str) -> List[VarInfo]:
        return [self.info_of(n) for n in self.op.inputs.get(slot, [])]

    def output(self, slot: str = 'Out') -> Optional[VarInfo]:
        names = self.op.outputs.get(slot, [])
        return self.info_of(names[0]) if names else None

    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    def elems(self, info: Optional[VarInfo]) -> int:
        return info_elems(info, self.assume_dim)

    def in_elems(self, slot: str) -> int:
        return self.elems(self.input(slot))

    def out_elems(self, slot: str = 'Out') -> int:
        names = self.op.outputs.get(slot, [])
        return sum(self.elems(self.info_of(n)) for n in names)

    def all_in_elems(self) -> int:
        return sum(self.elems(self.info_of(n))
                   for n in self.op.input_names())

    def all_out_elems(self) -> int:
        return sum(self.elems(self.info_of(n))
                   for n in self.op.output_names())


def op_flops(op, env: Dict[str, VarInfo], block, assume_dim=1) -> int:
    """FLOPs of one op under the current flow env (0 when no rule —
    plan.py reports the gap). plan.py calls this and prices bytes
    through its own per-name cache; :func:`op_cost` is the standalone
    API that computes both."""
    rule = _COST_RULES.get(op.type)
    if rule is None:
        return 0
    return max(int(rule(CostCtx(op, env, block, assume_dim))), 0)


def op_cost(op, env: Dict[str, VarInfo], block, assume_dim=1) -> OpCost:
    """Cost of one op under the current flow env. Bytes are always the
    generic Σ input/output VarInfo bytes; FLOPs come from the registered
    rule."""
    ctx = CostCtx(op, env, block, assume_dim)
    bytes_in = sum(info_nbytes(ctx.info_of(n), assume_dim)
                   for n in op.input_names())
    bytes_out = sum(info_nbytes(ctx.info_of(n), assume_dim)
                    for n in op.output_names())
    return OpCost(op_flops(op, env, block, assume_dim),
                  bytes_in, bytes_out)


# ---------------------------------------------------------------------------
# rules: elementwise / unary / comparisons
# ---------------------------------------------------------------------------

@cost_rule(*infer._ELTWISE_BINARY)
def _c_eltwise(ctx):
    return ctx.out_elems()


@cost_rule('fused_elemwise_add_activation')
def _c_fused_add_act(ctx):
    # one add + one activation per element; sigmoid/tanh transcendental
    f = 1 if ctx.attr('functor', 'relu') == 'relu' else TRANSCENDENTAL_FLOPS
    return (1 + f) * ctx.out_elems()


# transcendental members of the same-shape unary family
_TRANS_UNARY = frozenset((
    'exp', 'sqrt', 'rsqrt', 'cos', 'sin', 'acos', 'asin', 'cosh', 'sinh',
    'reciprocal', 'log', 'softplus', 'softsign', 'erf', 'logsigmoid',
    'atan', 'tanh_shrink', 'gelu', 'elu', 'selu', 'stanh', 'hard_swish',
    'swish', 'sigmoid', 'tanh', 'pow', 'l2_normalize'))


@cost_rule(*infer._SAME_SHAPE_UNARY, 'prelu')
def _c_unary(ctx):
    per = TRANSCENDENTAL_FLOPS if ctx.op.type in _TRANS_UNARY else 1
    return per * ctx.in_elems('x')


@cost_rule('softmax', 'log_softmax')
def _c_softmax(ctx):
    # exp + sum + div (+ log): priced as one transcendental pass + 2 linear
    return (TRANSCENDENTAL_FLOPS + 2) * ctx.in_elems('x')


@cost_rule('dropout')
def _c_dropout(ctx):
    return 2 * ctx.in_elems('x')        # mask draw + multiply


@cost_rule('cast', *infer._COMPARE)
def _c_per_elem(ctx):
    return ctx.out_elems()


@cost_rule('logical_not', 'isfinite', 'has_inf', 'has_nan')
def _c_bool_unary(ctx):
    return ctx.in_elems('x')


# ---------------------------------------------------------------------------
# rules: matmul family / reductions
# ---------------------------------------------------------------------------

def _dim(d, assume):
    return int(d) if known(d) else int(assume)


@cost_rule('matmul')
def _c_matmul(ctx):
    x, y = ctx.input('x'), ctx.input('y')
    k = None
    if x is not None and x.shape is not None and len(x.shape) >= 1:
        xs = list(x.shape)
        if ctx.attr('transpose_x', False) and len(xs) > 1:
            xs[-1], xs[-2] = xs[-2], xs[-1]
        k = _dim(xs[-1], ctx.assume_dim)
    elif y is not None and y.shape is not None and len(y.shape) >= 2:
        ys = list(y.shape)
        if ctx.attr('transpose_y', False):
            ys[-1], ys[-2] = ys[-2], ys[-1]
        k = _dim(ys[-2], ctx.assume_dim)
    return 2 * (k or 1) * ctx.out_elems()


@cost_rule('mul')
def _c_mul(ctx):
    x = ctx.input('x')
    xcd = ctx.attr('x_num_col_dims', 1)
    k = 1
    if x is not None and x.shape is not None:
        for d in x.shape[xcd:]:
            k *= _dim(d, ctx.assume_dim)
    return 2 * k * ctx.out_elems()


@cost_rule('dot')
def _c_dot(ctx):
    return 2 * ctx.in_elems('x')


@cost_rule(*infer._REDUCES, 'mean', 'cumsum')
def _c_reduce(ctx):
    return ctx.in_elems('x')


@cost_rule('logsumexp')
def _c_logsumexp(ctx):
    return (TRANSCENDENTAL_FLOPS + 1) * ctx.in_elems('x')


@cost_rule('sum')
def _c_sum_variadic(ctx):
    n = len(ctx.op.inputs.get('xs', []))
    return max(n - 1, 0) * ctx.out_elems()


# ---------------------------------------------------------------------------
# rules: data movement — 0 FLOPs, the generic byte accounting is the cost
# ---------------------------------------------------------------------------

_MOVE_OPS = ('reshape', 'transpose', 'squeeze', 'unsqueeze', 'concat',
             'split', 'stack', 'unstack', 'slice', 'flatten', 'flatten2',
             'expand', 'gather', 'one_hot', 'lookup_table', 'where', 'pad',
             'shape', 'fill_constant', 'fill_constant_batch_size_like',
             'fill_any_like', '__constant__', '__init__')


@cost_rule(*_MOVE_OPS)
def _c_move(ctx):
    return 0


@cost_rule('top_k', 'arg_max', 'arg_min')
def _c_select(ctx):
    return ctx.in_elems('x')            # one comparison sweep


# ---------------------------------------------------------------------------
# rules: nn
# ---------------------------------------------------------------------------

@cost_rule('conv2d')
def _c_conv2d(ctx):
    w = ctx.input('weight')
    if w is None or w.shape is None or len(w.shape) != 4:
        return 2 * ctx.out_elems()
    _oc, ic, kh, kw = (_dim(d, ctx.assume_dim) for d in w.shape)
    return 2 * ic * kh * kw * ctx.out_elems()


@cost_rule('pool2d')
def _c_pool2d(ctx):
    ks = ctx.attr('pool_size', 2)
    if ctx.attr('global_pooling', False) or ks in (-1, (-1, -1), [-1, -1]):
        return ctx.in_elems('x')
    ks = tuple(ks) if isinstance(ks, (list, tuple)) else (ks, ks)
    return int(ks[0]) * int(ks[1]) * ctx.out_elems()


@cost_rule('adaptive_pool2d')
def _c_adaptive_pool(ctx):
    return ctx.in_elems('x')


@cost_rule('batch_norm')
def _c_batch_norm(ctx):
    # stats (2 passes) + normalize (scale/shift/rsqrt) ≈ 10 flops/elem
    return 10 * ctx.in_elems('x')


@cost_rule('layer_norm', 'instance_norm', 'group_norm', 'lrn')
def _c_norm(ctx):
    return 10 * ctx.in_elems('x')


# ---------------------------------------------------------------------------
# rules: losses / metrics
# ---------------------------------------------------------------------------

@cost_rule('softmax_with_cross_entropy')
def _c_softmax_ce(ctx):
    return (TRANSCENDENTAL_FLOPS + 4) * ctx.in_elems('logits')


@cost_rule('cross_entropy')
def _c_cross_entropy(ctx):
    return (TRANSCENDENTAL_FLOPS + 1) * ctx.in_elems('x')


@cost_rule('square_error_cost')
def _c_square_error(ctx):
    return 3 * ctx.out_elems()


@cost_rule('sigmoid_cross_entropy_with_logits')
def _c_sigmoid_ce(ctx):
    return (TRANSCENDENTAL_FLOPS + 3) * ctx.in_elems('x')


@cost_rule('accuracy')
def _c_accuracy(ctx):
    return ctx.all_in_elems()


# ---------------------------------------------------------------------------
# rules: optimizer updates — factor × param elems (factor ≈ flops/elem of
# the update formula, from the kernel implementations in ops/optimizer_ops)
# ---------------------------------------------------------------------------

_OPT_FLOP_FACTORS = {
    'sgd': 2, 'momentum': 5, 'lars_momentum': 12, 'adam': 18, 'adamax': 12,
    'adagrad': 6, 'decayed_adagrad': 8, 'adadelta': 12, 'rmsprop': 12,
    'ftrl': 14, 'lamb': 24, 'dpsgd': 6, 'dgc_momentum': 10,
}


def _c_opt(ctx):
    factor = _OPT_FLOP_FACTORS.get(ctx.op.type, 8)
    return factor * ctx.in_elems('param')


for _t in infer._OPT_MIRROR:
    cost_rule(_t)(_c_opt)
if 'dgc_momentum' not in _COST_RULES:
    cost_rule('dgc_momentum')(_c_opt)


def _c_fused_opt(ctx):
    base = ctx.op.type[len('fused_'):]
    factor = _OPT_FLOP_FACTORS.get(base, 8)
    return factor * sum(ctx.elems(p) for p in ctx.inputs('params'))


for _t in infer._FUSED_OPT_MIRROR:
    cost_rule(_t)(_c_fused_opt)


def _c_sparse_opt(ctx):
    # rows-only scatter-apply: the update formula runs over the padded
    # COO vals (K × D), NOT the V × D table — that asymmetry vs the
    # dense family is the whole fast path (docs/SPARSE.md)
    base = ctx.op.type[len('sparse_'):]
    factor = _OPT_FLOP_FACTORS.get(base, 8)
    return factor * ctx.in_elems('vals')


for _t in infer._SPARSE_OPT_MIRROR:
    cost_rule(_t)(_c_sparse_opt)


# ---------------------------------------------------------------------------
# rules: collectives — local reduce math only; wire bytes are what the
# collective_* telemetry (PR 9) prices, not this model
# ---------------------------------------------------------------------------

@cost_rule('c_allreduce_sum', 'c_allreduce_max', 'c_allreduce_min',
           'c_allreduce_prod')
def _c_allreduce(ctx):
    return ctx.in_elems('x')


@cost_rule('c_allreduce_sum_bucket')
def _c_allreduce_bucket(ctx):
    return sum(ctx.elems(x) for x in ctx.inputs('xs'))


# ---------------------------------------------------------------------------
# rules: paged attention — the decode-pool read path. Bytes stay generic
# (Σ VarInfo nbytes), which is exactly the quantization story: an int8 pool
# prices its pages at 1 B/elem and its f32 row scales at 4 B/row with no
# op-specific bytes code here. FLOPs walk the padded context.
# ---------------------------------------------------------------------------

def _pdim(info, i, assume):
    """Dim `i` of a VarInfo, with unknown rank/dim priced at `assume`."""
    if info is None or info.shape is None or i >= len(info.shape):
        return int(assume)
    return int(info.shape[i]) if known(info.shape[i]) else int(assume)


@cost_rule('paged_attention', 'paged_prefill_attention')
def _c_paged_attention(ctx):
    # per query row against T_pad = num_blocks_per_seq × block_size keys:
    # QK^T (2D) + softmax (~TRANS+2) + PV (2D). The padded extent is what
    # the gathered reads do (masked positions still burn the lanes) and the
    # bound of the single-query read, whose work follows the contexts'
    # lengths, which a static rule cannot know
    # pages are (num_blocks, block_size, H·D) rows of one token; H and D
    # are q's: (S, H, D), (S, H, K, D) or prefill's (1, H, L, D)
    q = ctx.input('q')
    kp = ctx.input('k_pages')
    bt = ctx.input('block_tables')
    a = ctx.assume_dim
    heads = _pdim(q, 1, a)
    block_size = _pdim(kp, 1, a)
    head_dim = _pdim(q, -1, a)
    seqs = _pdim(bt, 0, a)
    t_pad = _pdim(bt, 1, a) * block_size
    if ctx.attr('kv_heads', None) is not None and ctx.op.type == \
            'paged_prefill_attention':
        # the causal grouped form attends the rung itself: half its square
        t_pad = max(1, _pdim(q, 2, a) // 2)
    span = int(ctx.attr('span', 0))
    if span:
        # a sliding layer: no row sees more than its span
        t_pad = min(t_pad, span)
    queries = max(1, ctx.out_elems() // max(1, head_dim))
    flops = queries * t_pad * (4 * head_dim + TRANSCENDENTAL_FLOPS + 2)
    if ctx.input('k_scales') is not None:
        # int8 pool: one dequant multiply per gathered K and V element
        # (the gather materializes every sequence's padded window once,
        # shared across that sequence's query rows)
        flops += 2 * seqs * heads * t_pad * head_dim
    return flops


# ---------------------------------------------------------------------------
# rules: the modern decoder block (ops/llm_ops.py). Bytes stay generic; the
# FLOPs are the mathematics' (a grouped expert matmul prices the
# assignments it is given, not experts x tokens).
# ---------------------------------------------------------------------------

@cost_rule('rms_norm')
def _c_rms_norm(ctx):
    return 4 * ctx.in_elems('x')


@cost_rule('rope')
def _c_rope(ctx):
    # sin and cos per pair, 6 multiply-adds per pair
    return (TRANSCENDENTAL_FLOPS + 3) * ctx.in_elems('x')


@cost_rule('lm_head')
def _c_lm_head(ctx):
    vocab = 0 if ctx.attr('tied', False) else 1
    return 2 * ctx.in_elems('x') * _pdim(ctx.input('w'), vocab,
                                         ctx.assume_dim)


@cost_rule('diffusion_pick')
def _c_diffusion_pick(ctx):
    # per logit a compare for the max, an exponential and an add
    return (TRANSCENDENTAL_FLOPS + 2) * ctx.in_elems('rows')


@cost_rule('swiglu_ffn')
def _c_swiglu_ffn(ctx):
    f = _pdim(ctx.input('w_gate'), 1, ctx.assume_dim)
    rows = ctx.in_elems('x') // max(1, _pdim(ctx.input('w_gate'), 0,
                                            ctx.assume_dim))
    return 6 * ctx.in_elems('x') * f + (TRANSCENDENTAL_FLOPS + 1) * rows * f


@cost_rule('sigmoid_gate')
def _c_sigmoid_gate(ctx):
    return (TRANSCENDENTAL_FLOPS + 1) * ctx.in_elems('x')


@cost_rule('moe_router')
def _c_moe_router(ctx):
    experts = _pdim(ctx.input('w_gate'), 1, ctx.assume_dim)
    tokens = _pdim(ctx.input('x'), 0, ctx.assume_dim)
    return 2 * ctx.in_elems('x') * experts \
        + TRANSCENDENTAL_FLOPS * tokens * experts


@cost_rule('moe_experts')
def _c_moe_experts(ctx):
    a = ctx.assume_dim
    gate = ctx.input('w_gate')
    h, f = _pdim(gate, 1, a), _pdim(gate, 2, a)
    assignments = ctx.in_elems('ids')
    # with experts_held (a share of the router's experts) this is the
    # bound: which assignments fall on the held experts a static rule
    # cannot know
    return assignments * (6 * h * f + (TRANSCENDENTAL_FLOPS + 1) * f + 2 * h)


def _mla_dims(ctx):
    a = ctx.assume_dim
    q, w = ctx.input('q'), ctx.input('w_kvb')
    return (_pdim(q, 0, a), _pdim(q, 1, a), _pdim(q, 2, a), _pdim(q, 3, a),
            _pdim(w, 0, a), int(ctx.attr('qk_nope_dim', 0)),
            int(ctx.attr('v_dim', 0)))


@cost_rule('mla_prefill_attention')
def _c_mla_prefill(ctx):
    b, length, heads, qk, rank, nope, v = _mla_dims(ctx)
    expand = 2 * b * length * rank * heads * (nope + v)
    attend = b * heads * length * length * (
        2 * qk + 2 * v + TRANSCENDENTAL_FLOPS + 2)
    return expand + attend


@cost_rule('mla_decode_attention')
def _c_mla_decode(ctx):
    # absorbed: W_UK into the query, the padded extent of latent rows read
    # once for all heads, W_UV after the sum. The lockstep read walks the
    # LIVE groups alone (ops/llm_ops.py), but how many are live is in the
    # context lengths' values: a rule over shapes prices the static bound,
    # every slot at the table's whole width
    s, k, heads, qk, rank, nope, v = _mla_dims(ctx)
    a = ctx.assume_dim
    pages, tables = ctx.input('pages'), ctx.input('block_tables')
    t_pad = _pdim(tables, 1, a) * _pdim(pages, 1, a)
    rope = qk - nope
    queries = s * k * heads
    return queries * (2 * nope * rank + 2 * rank * v
                      + t_pad * (2 * (rank + rope) + 2 * rank
                                 + TRANSCENDENTAL_FLOPS + 2))


@cost_rule('retention_gate')
def _c_retention_gate(ctx):
    groups = _pdim(ctx.input('w'), 1, ctx.assume_dim)
    rows = ctx.in_elems('x') // max(1, _pdim(ctx.input('w'), 0,
                                            ctx.assume_dim))
    return 2 * ctx.in_elems('x') * groups \
        + 2 * TRANSCENDENTAL_FLOPS * rows * groups


def _retention_dims(ctx):
    a = ctx.assume_dim
    q, k = ctx.input('q'), ctx.input('k')
    d = _pdim(q, 3, a)
    return (_pdim(q, 0, a), _pdim(q, 1, a), _pdim(q, 2, a), _pdim(k, 2, a),
            d, d * (d + 1) // 2)


@cost_rule('power_retention_step')
def _c_retention_step(ctx):
    # per slot: φ of every head, one read of the state per query head
    # (2·D·(d + 1)), and per key/value head the gate and the rank-one
    # update of its D × (d + 1) state
    slots, _, heads, groups, d, big = _retention_dims(ctx)
    return slots * ((heads + groups) * 2 * big
                    + heads * 2 * big * (d + 1)
                    + groups * 3 * big * (d + 1))


@cost_rule('power_retention_prefill')
def _c_retention_prefill(ctx):
    # as computed: per token and query head the scores and weighted sum
    # over every key of the padded sequence (2(2d + 1) + the gates a key);
    # per key/value head its φ(k) [v, 1]ᵀ into the next state
    b, length, heads, groups, d, big = _retention_dims(ctx)
    chunk = min(int(ctx.attr('chunk', 256)), length)
    per_key = 2 * (2 * d + 1) + TRANSCENDENTAL_FLOPS + 3
    per_query = -(-length // chunk) * chunk * per_key
    return b * length * (heads * per_query
                         + groups * (2 * big + 2 * big * (d + 1)))


@cost_rule('short_conv_prefill', 'short_conv_step')
def _c_short_conv(ctx):
    # per row and channel: u = B·z, L taps' products and their sum, the gate
    taps = _pdim(ctx.input('w'), 0, ctx.assume_dim)
    return ctx.in_elems('x') // 3 * (2 * taps + 1)


# ---------------------------------------------------------------------------
# fallback coverage: every remaining op type with an INFER rule gets a
# bytes-only cost rule so the registries stay coverage-aligned (the tier-1
# coverage test asserts infer rules ⊆ cost rules); genuinely-unknown op
# types stay unregistered and plan.py reports them as gaps.
# ---------------------------------------------------------------------------

def _c_bytes_only(ctx):
    return 0


for _t in infer.all_rules():
    if _t not in _COST_RULES:
        cost_rule(_t)(_c_bytes_only)
