"""Decoder-only LM of the `lfm2_moe` family (LiquidAI/LFM2-8B-A1B): a HYBRID
of gated short-convolution layers and grouped-head attention layers in one
model, pre-norm RMSNorm blocks, a dense gated feed-forward in the first
``num_dense_layers`` layers and sigmoid-routed experts (a selection bias, no
shared expert) after them, the final norm (`embedding_norm`) at the OUTPUT
and the head TIED to the embedding, parameters kept in ``dtype``.

    a  = operator_norm(x)
    conv layer:  [B | C | z] = W_in a;  u_t = B_t ⊙ z_t
                 c_t = Σ_j w[j] ⊙ u_{t-(L-1)+j}   (u before position 0 zero)
                 o_t = W_out (C_t ⊙ c_t)          (no activation anywhere)
    attn layer:  q = qnorm(W_q a), k = knorm(W_k a), v = W_v a;  q, k = RoPE
                 o = W_out softmax(q · k / sqrt(d), causal) v
    h  = x + o;  m = ffn_norm(h)
    y  = h + Dense(m)   or   h + Σ_chosen w_e E_e(m)
    logits = E · embedding_norm(y)

What a layer keeps of a request differs by its kind: an attention layer a K
and a V row of the key/value heads per TOKEN, a conv layer its last L - 1
values of u per REQUEST, whatever the context (ops/llm_ops.py "gated short
convolution"). The router is ops/llm_ops.py::moe_router with the family's
normaliser (`norm_epsilon` 1e-6); the experts are `RoutedExperts`
(models/latent_moe_lm.py) with no shared expert.

The forward contract is models/causal_lm.py's: ``model(ids, pos_ids=None,
cache=None)``. Whole-sequence (``cache=None``) convolves the sequence and
attends under the causal mask. Under the decode engine (serving/decode/
kv_cache.py "Hybrid models") a conv layer goes through
`CacheContext.attend_state` with the two conv ops handed in: a prefill
leaves (u_{P-2}, u_{P-1}) of the prompt's TRUE end in the request's state
row, a step shifts the new value in; an attention layer names its class as
it attends (`CacheContext.attend(span=0)`, a full layer: the grouped reads);
a prefill returns (1, 1, V), the prompt's last row.

The configuration takes the keys of the published `config.json` under their
own names and refuses a value it has no equations for. What `config.json`
does not carry (the tied head, heads of hidden / heads, the per-head norms
on q and k, the chunk order B, C, z, the tap order, the router's 1e-6) is
the benchmark configuration's `assumed` (benchmark/configs/lfm2_8b_a1b.json):
the operator, the attention, the norms and the head as the image's
`transformers.models.lfm2` has them (tests/framework/
test_hybrid_conv_moe_lm.py holds the reference's blocks to those classes);
the expert block is the issue's reading of the public `lfm2_moe`, which the
image does not have.
"""
from __future__ import annotations

import jax
import numpy as np

from ..dygraph import Embedding, Layer, LayerList
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .block_diffusion_lm import BlockAttention
from .latent_moe_lm import (GatedFFN, RMSNorm, RoutedExperts, _linear,
                            _scored_rows, check_published, from_published)

# what the block's equations assume of the published keys they do not read
_ONLY = {'conv_bias': False, 'rope_scaling': None, 'use_expert_bias': True,
         'tie_word_embeddings': True, 'block_auto_adjust_ff_dim': False}
# published keys that describe nothing of the forward
_IGNORED = ('model_type',)
LAYER_TYPES = ('conv', 'full_attention')
CONV_OPS = ('short_conv_prefill', 'short_conv_step')


class HybridConvMoEConfig:
    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers, num_dense_layers,
                 num_attention_heads, num_key_value_heads, num_experts,
                 num_experts_per_tok, layer_types, conv_L_cache=3,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 norm_eps=1e-5, rope_theta=1000000.0,
                 max_position_embeddings=4096, initializer_range=0.02,
                 router_bias_scale=0.0, conv_tap_scale=0.1,
                 router_norm_epsilon=1e-6, dtype='float32', **published):
        check_published('HybridConvMoEConfig', published, _ONLY, _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_dense_layers = int(num_dense_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f'HybridConvMoEConfig: {self.num_attention_heads} query '
                f'heads must divide a hidden size of {self.hidden_size} and '
                f'divide over {self.num_key_value_heads} key/value heads')
        # the family has no `head_dim` key: a head is hidden / heads
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.layer_types = tuple(layer_types)
        unknown = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if unknown or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f'HybridConvMoEConfig: layer_types must name one of '
                f'{LAYER_TYPES} for each of the {self.num_hidden_layers} '
                f'layers; got {len(self.layer_types)} entries'
                + (f', unknown: {unknown}' if unknown else ''))
        self.conv_L_cache = int(conv_L_cache)
        if self.conv_L_cache < 2:
            raise ValueError(f'HybridConvMoEConfig: conv_L_cache='
                             f'{conv_L_cache}: a filter of one tap is no '
                             f'convolution and keeps no state')
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError('HybridConvMoEConfig: num_experts_per_tok='
                             f'{num_experts_per_tok} of {self.num_experts}')
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(norm_eps)       # `RMSNorm`'s own name
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # `expert_bias` is a buffer, zero in a fresh checkpoint; a seeded
        # N(0, scale) tells choosing by s + b from weighting by s
        self.router_bias_scale = float(router_bias_scale)
        # the filter's taps, N(0, scale): the three of a channel differ. The
        # scale sets the branch's size beside the residual stream, and the
        # operator is cubic in its input: benchmark/configs/lfm2_8b_a1b.json
        # `departures` says what 0.5 cost against a float32 reference
        self.conv_tap_scale = float(conv_tap_scale)
        self.router_norm_epsilon = float(router_norm_epsilon)
        self.dtype = dtype
        # `RoutedExperts` (models/latent_moe_lm.py) under its own names
        self.n_routed_experts = self.num_experts
        self.n_shared_experts = 0
        self.scoring_func = 'sigmoid'
        # a prefill notes EVERY row's chosen experts (`engine.last_stats`,
        # 16 B a row a layer, read by whoever asks): a conv layer's row reads
        # the two rows before it directly, so a check against a reference
        # has to know how those were routed too
        self.note_every_rows_experts = True

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    def is_conv(self, layer):
        return self.layer_types[layer] == 'conv'

    @property
    def conv_state_block(self):
        """The float32 block a conv layer keeps a request: its last L - 1
        values of u, oldest first, h values on the lanes."""
        return (1, self.conv_L_cache - 1, self.hidden_size)

    @staticmethod
    def tiny(**overrides):
        """Test scale: a dense conv layer, then attention, conv, conv with 8
        experts top-2; 4 query heads over 2 key/value heads of 8."""
        sizes = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                     moe_intermediate_size=32, num_hidden_layers=4,
                     num_dense_layers=1, num_attention_heads=4,
                     num_key_value_heads=2, num_experts=8,
                     num_experts_per_tok=2,
                     layer_types=['conv', 'full_attention', 'conv', 'conv'],
                     rope_theta=1e6, max_position_embeddings=128,
                     initializer_range=0.2, router_bias_scale=0.05,
                     conv_tap_scale=0.5)
        sizes.update(overrides)
        return HybridConvMoEConfig(**sizes)


class ShortConv(Layer):
    """The gated short convolution from `W_in` to `W_out`; ``taps`` (L, h),
    tap j on position t - (L - 1) + j (torch Conv1d's weight (h, 1, L),
    transposed: h on the lanes)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.in_proj = _linear(cfg, cfg.hidden_size, 3 * cfg.hidden_size)
        self.out_proj = _linear(cfg, cfg.hidden_size, cfg.hidden_size)
        self.taps = self.create_parameter(
            [cfg.conv_L_cache, cfg.hidden_size], None, cfg.dtype,
            default_initializer=NormalInitializer(0.0, cfg.conv_tap_scale))

    def forward(self, x, cache=None):
        # the scopes name the operator's device ops, projections and all,
        # in a profiler trace
        step = cache is not None and cache.mode != 'prefill'
        with jax.named_scope('conv/step' if step else 'conv/prefill'):
            inputs = {'x': self.in_proj(x), 'w': self.taps}
            if cache is None:
                out, _ = dispatch_op(CONV_OPS[0], inputs, {})
            else:
                out = cache.attend_state(CONV_OPS, inputs, {},
                                         self.cfg.conv_state_block)
            return self.out_proj(out)


class GroupedAttention(BlockAttention):
    """`BlockAttention`'s projections, per-head norms and head helpers
    (models/block_diffusion_lm.py: grouped key/value heads, RMSNorm on q and
    k before RoPE) under the causal mask: through the cache a FULL layer of
    a model that names its classes (the grouped reads)."""

    def forward(self, x, pos_ids, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        rope = {'theta': cfg.rope_theta}
        q = dispatch_op('rope', {'x': self.q_norm(
            self._heads(self.q_proj(x), heads, b, s)), 'pos': pos_ids}, rope)
        k = dispatch_op('rope', {'x': self.k_norm(
            self._heads(self.k_proj(x), groups, b, s)), 'pos': pos_ids}, rope)
        v = self._heads(self.v_proj(x), groups, b, s)
        q, k, v = (self._head_major(t) for t in (q, k, v))   # (B, n, S, d)
        scale = d ** -0.5
        if cache is not None:
            out = cache.attend(q, k, v, sm_scale=scale, span=0)
        else:
            rep = heads // groups
            if rep > 1:
                k, v = (self._repeat(t, rep, b, s) for t in (k, v))
            out = dispatch_op('fused_attention', {'q': q, 'k': k, 'v': v},
                              {'sm_scale': scale, 'causal': True})
        out = dispatch_op('reshape', {'x': self._head_major(out)},
                          {'shape': [b, s, heads * d]})
        return self.o_proj(out)


class HybridConvMoEBlock(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.operator_norm = RMSNorm(cfg, cfg.hidden_size)
        self.is_conv = cfg.is_conv(index)
        self.operator = ShortConv(cfg) if self.is_conv \
            else GroupedAttention(cfg)
        self.ffn_norm = RMSNorm(cfg, cfg.hidden_size)
        self.routed = index >= cfg.num_dense_layers
        self.ffn = RoutedExperts(cfg) if self.routed \
            else GatedFFN(cfg, cfg.intermediate_size)

    def forward(self, x, pos_ids, cache=None):
        a = self.operator_norm(x)
        x = x + (self.operator(a, cache) if self.is_conv
                 else self.operator(a, pos_ids, cache))
        m = self.ffn_norm(x)
        return x + (self.ffn(m, cache) if self.routed else self.ffn(m))


class HybridConvMoELM(Layer):
    def __init__(self, cfg: HybridConvMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([HybridConvMoEBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        # the family's name for the final norm: it sits at the OUTPUT
        self.embedding_norm = RMSNorm(cfg, cfg.hidden_size)

    def cache_layout(self):
        """What the decode engine caches of this model, a layer at a time: a
        conv layer one float32 state a REQUEST (``conv_state_block``,
        advanced by the short convolution), an attention layer K and V rows
        of the key/value heads a TOKEN, full layers read over the live
        groups (serving/decode/layout.py, kv_cache.py "Hybrid models")."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        conv = LayerCache.state(cfg.conv_state_block, 'short_conv')
        attention = LayerCache.kv(cfg.num_key_value_heads, cfg.head_dim,
                                  read='groups')
        return CacheLayout(tuple(conv if cfg.is_conv(i) else attention
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
        for block in self.layers:
            x = block(x, pos_ids, cache)
        if cache is not None:
            x = Tensor(_scored_rows(cache, x.value, 1), stop_gradient=True)
        # the head reads the embedding's own array
        return dispatch_op('lm_head', {'x': self.embedding_norm(x),
                                       'w': self.embed.weight},
                           {'tied': True})
