"""Decoder-only LM of the `afmoe` family (arcee-ai/Trinity-Large-Preview,
Trinity-Mini): SLIDING-window and FULL attention layers mixed in one model,
grouped key/value heads with per-head RMSNorm on q and k, a sigmoid GATE on
the attention output, sandwich norms (a norm on each branch's output as
well as its input), a dense gated feed-forward in the first
``num_dense_layers`` layers and sigmoid-routed experts with one shared
expert after them, an untied head, parameters kept in ``dtype``.

    x  = E[ids] · sqrt(hidden)                            (mup_enabled)
    a  = n1(x);  q = qnorm(W_q a), k = knorm(W_k a), v = W_v a, g = W_g a
    sliding layer: q, k = RoPE(q, k);  key j visible to row i iff 0 <= i - j < window
    full layer:    no position encoding at all;  key j visible iff j <= i
    o  = softmax(q · k / sqrt(d)) v  ⊙  sigmoid(g)
    h  = x + n2(W_o o);  m = n3(h)
    y  = h + n4(Dense(m))   or   h + n4(Shared(m) + Σ_chosen w_e E_e(m))
    logits = W_head · norm(y)

The router is ops/llm_ops.py::moe_router as it stands (sigmoid scores, the
top-k of s + b chosen, the unbiased s normalised over the chosen and
scaled). ``experts_held`` = (first, count) makes an expert layer ONE CHIP'S
SHARE of a layer spread over several (expert parallelism): the router keeps
its ``router_width`` outputs and its top-k, the layer holds the weights of
experts [first, first + count) alone and returns Shared(m) plus the part of
the routed sum its own experts give (`RoutedExperts`, models/latent_moe_lm.py).
No code stands in for the absent chips or their exchange.

The forward contract is models/causal_lm.py's: ``model(ids, pos_ids=None,
cache=None)``. Whole-sequence (``cache=None``) attends under each layer's
mask as an additive bias. Under the decode engine every layer names its
class as it attends (`CacheContext.attend(span=)`, 0 a full layer): a full
layer's K/V live in a table that grows with the context, a sliding layer's
in a ring of window / block + 1 blocks (serving/decode/kv_cache.py "Layer
classes"); a prefill returns (1, 1, V), the prompt's last row.

The configuration takes the keys of the published `config.json` under their
own names and refuses a value it has no equations for. What `config.json`
does not carry (the four norms and where they sit, the gate projection, the
per-head norms on q and k, rotary positions on the sliding layers alone,
the embedding's sqrt(hidden) under `mup_enabled`) is the benchmark
configuration's `assumed` (benchmark/configs/trinity_large_preview.json):
as the public `transformers` implementation of `afmoe` has them, and not
held against that source here (no network, and the image's `transformers`
has no `afmoe`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..dygraph import Embedding, Layer, LayerList
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .block_diffusion_lm import BlockAttention
from .latent_moe_lm import (GatedFFN, RMSNorm, RoutedExperts, _linear,
                            _scored_rows, check_published, from_published)

# what the block's equations assume of the published keys they do not read
_ONLY = {'rope_scaling': None, 'n_group': 1, 'topk_group': 1,
         'num_expert_groups': 1, 'num_limited_groups': 1,
         'score_func': 'sigmoid', 'tie_word_embeddings': False,
         'hidden_act': 'silu'}
# published keys that repeat another or describe nothing of the forward:
# `global_attn_every_n_layers` repeats `layer_types`, `load_balance_coeff`
# is the trainer's, `use_grouped_mm` an implementation's choice
_IGNORED = ('model_type', 'global_attn_every_n_layers',
            'load_balance_coeff', 'use_grouped_mm')
LAYER_TYPES = ('sliding_attention', 'full_attention')


class SlidingMoEConfig:
    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers, num_dense_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 num_experts, num_experts_per_tok, layer_types,
                 sliding_window, num_shared_experts=1, route_norm=True,
                 route_scale=1.0, mup_enabled=True, rms_norm_eps=1e-5,
                 rope_theta=10000.0, max_position_embeddings=4096,
                 initializer_range=0.02, router_bias_scale=0.0,
                 experts_held=None, router_width=None, dtype='float32',
                 **published):
        check_published('SlidingMoEConfig', published, _ONLY, _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_dense_layers = int(num_dense_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'SlidingMoEConfig: {self.num_attention_heads} query heads '
                f'do not divide over {self.num_key_value_heads} key/value '
                f'heads')
        self.layer_types = tuple(layer_types)
        unknown = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if unknown or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f'SlidingMoEConfig: layer_types must name one of '
                f'{LAYER_TYPES} for each of the {self.num_hidden_layers} '
                f'layers; got {len(self.layer_types)} entries'
                + (f', unknown: {unknown}' if unknown else ''))
        self.sliding_window = int(sliding_window)
        if self.sliding_window < 1:
            raise ValueError(f'SlidingMoEConfig: sliding_window='
                             f'{sliding_window} holds no position')
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_shared_experts = int(num_shared_experts)
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.mup_enabled = bool(mup_enabled)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # `expert_bias` is a buffer, zero in a fresh checkpoint; a seeded
        # N(0, scale) tells choosing by s + b from weighting by s
        self.router_bias_scale = float(router_bias_scale)
        self.dtype = dtype
        # `num_experts` counts the experts whose weights this model holds;
        # the router is `router_width` wide (the published count, where
        # this is one chip's share of a layer) and ``experts_held`` says
        # which of its experts those are
        self.num_experts = int(num_experts)
        self.router_width = int(router_width or self.num_experts)
        if experts_held is None:
            if self.router_width != self.num_experts:
                raise ValueError(
                    f'SlidingMoEConfig: a router of {self.router_width} '
                    f'over {self.num_experts} experts needs experts_held')
        else:
            first, count = (int(n) for n in experts_held)
            if count != self.num_experts or first < 0 \
                    or first + count > self.router_width:
                raise ValueError(
                    f'SlidingMoEConfig: experts_held={tuple(experts_held)!r}'
                    f' is no range of {self.num_experts} of the router\'s '
                    f'{self.router_width} experts')
            experts_held = (first, count)
        self.experts_held = experts_held
        if self.num_experts_per_tok > self.router_width:
            raise ValueError('SlidingMoEConfig: num_experts_per_tok='
                             f'{num_experts_per_tok} of {self.router_width}')
        # `RoutedExperts` (models/latent_moe_lm.py) under its own names
        self.n_routed_experts = self.router_width
        self.n_shared_experts = self.num_shared_experts
        self.scoring_func = 'sigmoid'
        self.routed_scaling_factor = self.route_scale
        self.norm_topk_prob = self.route_norm

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    def span(self, layer):
        """The layer's class as `CacheContext.attend` takes it: its window,
        0 for a full layer."""
        return self.sliding_window \
            if self.layer_types[layer] == 'sliding_attention' else 0

    @staticmethod
    def tiny(**overrides):
        """Test scale: one dense sliding layer, then sliding, sliding, full
        with 8 experts top-2 and a shared one; 4 query heads over 2
        key/value heads of 8; a window of 8."""
        sizes = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                     moe_intermediate_size=32, num_hidden_layers=4,
                     num_dense_layers=1, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=8, num_experts=8,
                     num_experts_per_tok=2, num_shared_experts=1,
                     layer_types=['sliding_attention', 'sliding_attention',
                                  'sliding_attention', 'full_attention'],
                     sliding_window=8, route_scale=2.448, rope_theta=1e4,
                     max_position_embeddings=128, initializer_range=0.2,
                     router_bias_scale=0.05)
        sizes.update(overrides)
        return SlidingMoEConfig(**sizes)


def span_mask_bias(length, span, dtype=jnp.float32):
    """(length, length) additive mask: 0 where key j is visible to row i
    (j <= i, and with ``span`` S > 0 only while i - j < S), the dtype's most
    negative value elsewhere."""
    i = np.arange(length)[:, None]
    j = np.arange(length)[None, :]
    seen = (j <= i) & ((i - j < span) if span else True)
    return jnp.where(jnp.asarray(seen), 0.0, jnp.finfo(dtype).min
                     ).astype(dtype)


class GatedAttention(BlockAttention):
    """`BlockAttention`'s projections, per-head norms and head helpers
    (models/block_diffusion_lm.py: grouped key/value heads, RMSNorm on q and
    k), with a gate projection beside them, the layer's class, and a
    forward of its own: rotary positions on a sliding layer alone, the
    layer's mask, the gate."""

    def __init__(self, cfg, index):
        super().__init__(cfg)
        self.span = cfg.span(index)
        self.gate_proj = _linear(
            cfg, cfg.hidden_size, cfg.num_attention_heads * cfg.head_dim)

    def forward(self, x, pos_ids, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        q = self.q_norm(self._heads(self.q_proj(x), heads, b, s))
        k = self.k_norm(self._heads(self.k_proj(x), groups, b, s))
        if self.span:
            # rotary positions on the sliding layers alone: a full layer
            # has no position encoding at all
            rope = {'theta': cfg.rope_theta}
            q = dispatch_op('rope', {'x': q, 'pos': pos_ids}, rope)
            k = dispatch_op('rope', {'x': k, 'pos': pos_ids}, rope)
        v = self._heads(self.v_proj(x), groups, b, s)
        q, k, v = (self._head_major(t) for t in (q, k, v))   # (B, n, S, d)
        scale = d ** -0.5
        if cache is not None:
            out = cache.attend(q, k, v, sm_scale=scale, span=self.span)
        else:
            # every query head its own copy of its key/value head, and the
            # layer's mask as a bias: the plain form the paged reads are
            # held to (tests/framework/test_sliding_moe_lm.py)
            rep = heads // groups
            if rep > 1:
                k, v = (self._repeat(t, rep, b, s) for t in (k, v))
            out = dispatch_op('fused_attention', {
                'q': q, 'k': k, 'v': v,
                'bias': span_mask_bias(s, self.span)}, {'sm_scale': scale})
        out = dispatch_op('reshape', {'x': self._head_major(out)},
                          {'shape': [b, s, heads * d]})
        # the scope names the gate's device ops in a profiler trace
        with jax.named_scope('attn/gate'):
            out = dispatch_op('sigmoid_gate', {'x': out,
                                               'gate': self.gate_proj(x)}, {})
        return self.o_proj(out)


class SlidingMoEBlock(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.norm1 = RMSNorm(cfg, cfg.hidden_size)
        self.attn = GatedAttention(cfg, index)
        self.norm2 = RMSNorm(cfg, cfg.hidden_size)    # on the branch's output
        self.norm3 = RMSNorm(cfg, cfg.hidden_size)
        self.routed = index >= cfg.num_dense_layers
        self.ffn = RoutedExperts(cfg) if self.routed \
            else GatedFFN(cfg, cfg.intermediate_size)
        self.norm4 = RMSNorm(cfg, cfg.hidden_size)    # on the branch's output

    def forward(self, x, pos_ids, cache=None):
        x = x + self.norm2(self.attn(self.norm1(x), pos_ids, cache))
        m = self.norm3(x)
        return x + self.norm4(self.ffn(m, cache) if self.routed
                              else self.ffn(m))


class SlidingMoELM(Layer):
    def __init__(self, cfg: SlidingMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([SlidingMoEBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_norm = RMSNorm(cfg, cfg.hidden_size)
        self.head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def cache_layout(self):
        """What the decode engine caches of this model: K and V rows of the
        KEY/VALUE heads per token per layer, read a group of query heads at
        a time, and each layer's class: its span (0: a full layer, whose
        table grows with the context, read over the live groups; S: a
        sliding layer, which holds the last S positions in a ring, read over
        the ring's groups) (serving/decode/layout.py)."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        return CacheLayout(tuple(
            LayerCache.kv(cfg.num_key_value_heads, cfg.head_dim,
                          read='ring' if cfg.span(i) else 'groups',
                          span=cfg.span(i))
            for i in range(cfg.num_hidden_layers)))

    def forward(self, input_ids, pos_ids=None, cache=None):
        """``input_ids`` (B, S) -> float32 logits (B, S, V); ``pos_ids``
        (B, S) defaults to 0..S-1 per row. Under the decode engine a prefill
        returns (1, 1, V): the prompt's last row, the one the host reads."""
        b, s = input_ids.shape
        if pos_ids is None:
            pos_ids = Tensor(
                np.arange(s, dtype=np.int64)[None, :].repeat(b, 0),
                stop_gradient=True)
        # lookup_table squeezes a (B, 1) id column: restore (B, S, h)
        x = dispatch_op('reshape', {'x': self.embed(input_ids)},
                        {'shape': [b, s, self.cfg.hidden_size]})
        if self.cfg.mup_enabled:
            # in float32: `scale` would round sqrt(hidden) to x's dtype
            # (55.43 to bf16's 55.5, every row off by the same 0.13%)
            x = dispatch_op('cast', {'x': dispatch_op('scale', {
                'x': dispatch_op('cast', {'x': x}, {'dtype': 'float32'})},
                {'scale': math.sqrt(self.cfg.hidden_size)})},
                {'dtype': self.cfg.dtype})
        for block in self.layers:
            x = block(x, pos_ids, cache)
        if cache is not None:
            x = Tensor(_scored_rows(cache, x.value, 1), stop_gradient=True)
        return dispatch_op('lm_head', {'x': self.final_norm(x),
                                       'w': self.head.weight}, {})
