"""Decoder-only LM of the `nemotron_h` family (NVIDIA Nemotron-H, Nemotron 3
Super): a HYBRID whose layers are each ONE pre-norm mixer, of three kinds,
in the order `hybrid_override_pattern` spells: 'M' a Mamba-2 state-space
mixer, 'E' a latent mixture of relu² experts, '*' grouped-head attention
without rotary positions. The final norm `norm_f` sits before an untied
head; parameters are kept in ``dtype``.

    x_{i+1} = x_i + Mixer_i(RMSNorm_i(x_i))
    'M':  the mixer of ops/llm_ops.py "Mamba-2", from W_in to W_out
    'E':  s = sigmoid(W_r a) over the router's experts; the top k of s + b
          chosen, w_e = scale · s_e / Σ_chosen s;  ℓ = W_↓ a (h -> latent)
          o = W_↑ Σ_chosen w_e · W2_e relu(W1_e ℓ)²  +  W_s2 relu(W_s1 a)²
    '*':  q = W_q a, k = W_k a, v = W_v a (no norm, no rotary turn)
          o = W_o softmax(q · k / sqrt(d), causal) v, key/value heads shared
          by groups of query heads
    logits = head(norm_f(x))

What a layer keeps of a request differs by its kind: an attention layer a K
and a V row of the key/value heads per TOKEN, a Mamba layer ONE float32
state per REQUEST (its S and the last K - 1 rows of xBC, ``ssm_state_block``),
an expert layer nothing. Under the decode engine (serving/decode/kv_cache.py
"Hybrid models") a Mamba layer goes through `CacheContext.attend_state` with
the two SSM ops handed in; an attention layer names its class as it attends
(`CacheContext.attend(span=0)`, a full layer: the grouped reads); a prefill
returns (1, 1, V), the prompt's last row. The expert layer is
`RoutedExperts` (models/latent_moe_lm.py) in its latent, non-gated form.

The configuration takes the keys of the published `config.json` under their
own names and refuses a value, or a letter of the pattern, it has no
equations for. What `config.json` does not say (no rotary turn although
`rope_theta` is given, the latent projections' places, the router reading
the full-width a) is the benchmark configuration's `assumed`
(benchmark/configs/nemotron3_super_120b_a12b.json). Multi-token prediction
(`num_nextn_predict_layers`) is not held: the configuration refuses any
other value than 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.dtypes import to_jax_dtype
from ..dygraph import Embedding, Layer, LayerList
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import (ConstantInitializer, Initializer,
                           NormalInitializer, UniformInitializer)
from ..param_attr import ParamAttr
from .block_diffusion_lm import BlockAttention
from .latent_moe_lm import (RMSNorm, RoutedExperts, _linear, _scored_rows,
                            check_published, from_published)

# what the equations assume of the published keys they do not read
_ONLY = {'attention_bias': False, 'mamba_hidden_act': 'silu',
         'mamba_proj_bias': False, 'mlp_bias': False,
         'mlp_hidden_act': 'relu2', 'n_group': 1, 'topk_group': 1,
         'use_bias': False, 'use_conv_bias': True,
         'tie_word_embeddings': False, 'sliding_window': None,
         'residual_in_fp32': False, 'n_shared_experts': 1}
# published keys that repeat another or describe nothing of the forward:
# the rotary keys and the positions they were sized for (no rotary turn is
# applied, `assumed`), the width of a dense MLP layer (no '-' letter is
# served), the second name of the eps, how many logits a call keeps,
# initialisation, kernel and overlap choices, and the pattern of the
# multi-token-prediction layers, which are not held
_IGNORED = ('model_type', 'rope_theta', 'partial_rotary_factor',
            'max_position_embeddings', 'intermediate_size', 'norm_eps',
            'num_logits_to_keep', 'rescale_prenorm_residual',
            'use_mamba_kernels',
            'moe_shared_expert_overlap', 'mtp_hybrid_override_pattern')
KINDS = {'M': 'mamba', 'E': 'experts', '*': 'attention'}
SSM_OPS = ('ssm_scan_prefill', 'ssm_scan_step')


class HybridSSMMoEConfig:
    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 hybrid_override_pattern, num_attention_heads,
                 num_key_value_heads, head_dim, mamba_num_heads,
                 mamba_head_dim, n_groups, ssm_state_size, n_routed_experts,
                 num_experts_per_tok, moe_intermediate_size, moe_latent_size,
                 moe_shared_expert_intermediate_size, conv_kernel=4, expand=2,
                 chunk_size=128, routed_scaling_factor=1.0,
                 norm_topk_prob=True, layer_norm_epsilon=1e-5,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, num_nextn_predict_layers=0,
                 initializer_range=0.02, experts_held=None, router_width=None,
                 router_bias_scale=0.0, dtype='float32', **published):
        check_published('HybridSSMMoEConfig', published, _ONLY, _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.pattern = str(hybrid_override_pattern)
        unknown = sorted(set(self.pattern) - set(KINDS))
        if unknown or len(self.pattern) != self.num_hidden_layers:
            raise ValueError(
                f'HybridSSMMoEConfig: hybrid_override_pattern must spell one '
                f'of {sorted(KINDS)} for each of the {self.num_hidden_layers}'
                f' layers; got {len(self.pattern)} letters'
                + (f', no equations for {unknown}' if unknown else ''))
        if int(num_nextn_predict_layers):
            raise ValueError(
                f'HybridSSMMoEConfig: num_nextn_predict_layers='
                f'{num_nextn_predict_layers}: the multi-token-prediction '
                f'layers are not held (a draft over state layers needs a '
                f'state snapshot to roll back to)')
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'HybridSSMMoEConfig: {self.num_attention_heads} query heads '
                f'do not divide over {self.num_key_value_heads} key/value '
                f'heads')
        self.mamba_num_heads = int(mamba_num_heads)
        self.mamba_head_dim = int(mamba_head_dim)
        self.n_groups = int(n_groups)
        self.ssm_state_size = int(ssm_state_size)
        self.conv_kernel = int(conv_kernel)
        self.chunk_size = int(chunk_size)
        self.d_inner = self.mamba_num_heads * self.mamba_head_dim
        if self.d_inner != int(expand) * self.hidden_size \
                or self.mamba_num_heads % self.n_groups \
                or self.conv_kernel < 2:
            raise ValueError(
                f'HybridSSMMoEConfig: {self.mamba_num_heads} Mamba heads of '
                f'{self.mamba_head_dim} are not expand={expand} x '
                f'{self.hidden_size}, do not divide over {self.n_groups} '
                f'groups, or a filter of {conv_kernel} taps keeps no state')
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.ssm_state_size
        self.time_step_min = float(time_step_min)
        self.time_step_max = float(time_step_max)
        self.time_step_floor = float(time_step_floor)
        self.rms_norm_eps = float(layer_norm_epsilon)   # `RMSNorm`'s name
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        # the experts: `n_routed_experts` counts those whose weights this
        # model holds; the router is `router_width` wide (the published
        # count, where this is one chip's share of a layer) and
        # ``experts_held`` says which of its experts those are
        self.num_experts_held = int(n_routed_experts)
        self.router_width = int(router_width or self.num_experts_held)
        if experts_held is None:
            if self.router_width != self.num_experts_held:
                raise ValueError(
                    f'HybridSSMMoEConfig: a router of {self.router_width} '
                    f'over {self.num_experts_held} experts needs '
                    f'experts_held')
        else:
            first, count = (int(n) for n in experts_held)
            if count != self.num_experts_held or first < 0 \
                    or first + count > self.router_width:
                raise ValueError(
                    f'HybridSSMMoEConfig: experts_held='
                    f'{tuple(experts_held)!r} is no range of '
                    f'{self.num_experts_held} of the router\'s '
                    f'{self.router_width} experts')
            experts_held = (first, count)
        self.experts_held = experts_held
        self.num_experts_per_tok = int(num_experts_per_tok)
        if self.num_experts_per_tok > self.router_width:
            raise ValueError('HybridSSMMoEConfig: num_experts_per_tok='
                             f'{num_experts_per_tok} of {self.router_width}')
        # `RoutedExperts` (models/latent_moe_lm.py) under its own names: the
        # latent, non-gated form, every row's choices noted in a prefill (a
        # Mamba layer's row reads the rows before it through its state, so
        # a check against a reference has to know how those were routed)
        self.n_routed_experts = self.router_width
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.moe_latent_size = int(moe_latent_size)
        self.shared_expert_intermediate_size = int(
            moe_shared_expert_intermediate_size)
        self.n_shared_experts = 1
        self.expert_activation = 'relu2'
        self.scoring_func = 'sigmoid'
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        # `e_score_correction_bias` is zero in a fresh checkpoint; a seeded
        # N(0, scale) tells choosing by s + b from weighting by s
        self.router_bias_scale = float(router_bias_scale)
        self.note_every_rows_experts = True

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    def kind(self, layer):
        return KINDS[self.pattern[layer]]

    @property
    def ssm_state_block(self):
        """The float32 block a Mamba layer keeps a request: (P·N + W, H), the
        heads on the lanes: S's rows, then the filter's window
        (ops/llm_ops.py::ssm_state_width)."""
        from ..ops.llm_ops import ssm_state_width
        return (sum(ssm_state_width(
            self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size,
            self.conv_dim, self.conv_kernel)), self.mamba_num_heads)

    @staticmethod
    def tiny(**overrides):
        """Test scale: M E M * E; 8 Mamba heads of 8 over 2 groups of 4
        state values, chunks of 4; 8 experts top-2 in a latent of 16;
        attention 4 query heads over 2 key/value heads of 8."""
        sizes = dict(vocab_size=96, hidden_size=32, num_hidden_layers=5,
                     hybrid_override_pattern='MEM*E', num_attention_heads=4,
                     num_key_value_heads=2, head_dim=8, mamba_num_heads=8,
                     mamba_head_dim=8, n_groups=2, ssm_state_size=4,
                     n_routed_experts=8, num_experts_per_tok=2,
                     moe_intermediate_size=16, moe_latent_size=16,
                     moe_shared_expert_intermediate_size=32, chunk_size=4,
                     routed_scaling_factor=2.5, initializer_range=0.2,
                     router_bias_scale=0.05)
        sizes.update(overrides)
        return HybridSSMMoEConfig(**sizes)


class _Drawn(Initializer):
    """A parameter drawn by ``draw(key, shape)`` in float32, cast to the
    parameter's dtype."""

    def __init__(self, draw):
        self.draw = draw

    def compute(self, shape, dtype, key=None):
        return self.draw(self._key(key), tuple(shape)).astype(
            to_jax_dtype(dtype))


def _dt_bias(cfg):
    """softplus⁻¹ of a Δ drawn log-uniform in [time_step_min,
    time_step_max] and floored at time_step_floor (the family's
    initialisation)."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def draw(key, shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                    lo, hi)),
                         cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return _Drawn(draw)


class MambaMixer(Layer):
    """The Mamba-2 mixer from W_in to W_out (ops/llm_ops.py "Mamba-2"):
    ``conv_w`` (K, conv_dim), tap j on position t - (K - 1) + j (torch
    Conv1d's weight (conv_dim, 1, K), transposed: the channels on the
    lanes)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        heads = cfg.mamba_num_heads
        self.in_proj = _linear(cfg, cfg.hidden_size,
                               cfg.d_inner + cfg.conv_dim + heads)
        # torch Conv1d's own default: U(±1/sqrt(fan_in)), fan_in = K taps
        bound = 1.0 / math.sqrt(cfg.conv_kernel)
        self.conv_w = self.create_parameter(
            [cfg.conv_kernel, cfg.conv_dim], None, cfg.dtype,
            default_initializer=UniformInitializer(-bound, bound))
        self.conv_b = self.create_parameter(
            [cfg.conv_dim], None, cfg.dtype,
            default_initializer=UniformInitializer(-bound, bound))
        self.dt_bias = self.create_parameter([heads], None, 'float32',
                                             default_initializer=_dt_bias(cfg))
        self.A_log = self.create_parameter(
            [heads], None, 'float32', default_initializer=_Drawn(
                lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, jnp.float32, 1.0, 16.0))))
        self.D = self.create_parameter(
            [heads], None, 'float32',
            default_initializer=ConstantInitializer(1.0))
        self.norm = self.create_parameter(
            [cfg.d_inner], None, cfg.dtype,
            default_initializer=ConstantInitializer(1.0))
        self.out_proj = _linear(cfg, cfg.d_inner, cfg.hidden_size)
        self._attrs = {'head_dim': cfg.mamba_head_dim, 'groups': cfg.n_groups,
                       'epsilon': cfg.rms_norm_eps}

    def forward(self, a, cache=None):
        # the scopes name the mixer's device ops, projections and all, in a
        # profiler trace
        step = cache is not None and cache.mode != 'prefill'
        with jax.named_scope('ssm/step' if step else 'ssm/prefill'):
            inputs = {'x': a, 'w_in': self.in_proj.weight,
                      'conv_w': self.conv_w, 'conv_b': self.conv_b,
                      'dt_bias': self.dt_bias, 'a_log': self.A_log,
                      'd': self.D, 'norm_w': self.norm}
            prefill = dict(self._attrs, chunk=self.cfg.chunk_size)
            if cache is None:
                out, _ = dispatch_op(SSM_OPS[0], inputs, prefill)
            else:
                out = cache.attend_state(SSM_OPS, inputs, prefill,
                                         self.cfg.ssm_state_block,
                                         step_attrs=self._attrs)
            return self.out_proj(out)


class GroupedAttention(BlockAttention):
    """`BlockAttention`'s projections and head helpers (models/
    block_diffusion_lm.py) with NO per-head norm and NO rotary turn, under
    the causal mask: through the cache a FULL layer of a model that names
    its classes (the grouped reads)."""

    def __init__(self, cfg):
        Layer.__init__(self)
        self.cfg = cfg
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = _linear(cfg, cfg.hidden_size, heads * d)
        self.k_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.v_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.o_proj = _linear(cfg, heads * d, cfg.hidden_size)

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        q, k, v = (self._head_major(self._heads(proj(x), n, b, s))
                   for proj, n in ((self.q_proj, heads),
                                   (self.k_proj, groups),
                                   (self.v_proj, groups)))  # (B, n, S, d)
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


_MIXERS = {'mamba': MambaMixer, 'experts': RoutedExperts,
           'attention': GroupedAttention}


class HybridSSMMoEBlock(Layer):
    """One layer: x + Mixer(RMSNorm(x)), the mixer of the layer's letter."""

    def __init__(self, cfg, index):
        super().__init__()
        self.norm = RMSNorm(cfg, cfg.hidden_size)
        self.mixer = _MIXERS[cfg.kind(index)](cfg)

    def forward(self, x, cache=None):
        return x + self.mixer(self.norm(x), cache)


class HybridSSMMoELM(Layer):
    def __init__(self, cfg: HybridSSMMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([HybridSSMMoEBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm_f = RMSNorm(cfg, cfg.hidden_size)
        self.head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def cache_layout(self):
        """What the decode engine caches of this model, a layer at a time: a
        Mamba layer one float32 state a REQUEST (``ssm_state_block``,
        advanced by the SSM scan), an attention layer K and V rows of the
        key/value heads a TOKEN, full layers read over the live groups
        (serving/decode/layout.py, kv_cache.py "Hybrid models"); an expert
        layer caches nothing."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        ssm = LayerCache.state(cfg.ssm_state_block, 'ssm')
        attention = LayerCache.kv(cfg.num_key_value_heads, cfg.head_dim,
                                  read='groups')
        return CacheLayout(tuple(
            ssm if kind == 'mamba' else attention
            for kind in map(cfg.kind, range(cfg.num_hidden_layers))
            if kind != 'experts'))

    def forward(self, input_ids, pos_ids=None, cache=None):
        """``input_ids`` (B, S) -> float32 logits (B, S, V); ``pos_ids`` is
        taken for the causal-LM contract and read by no layer (no rotary
        turn: the Mamba layers carry position). Under the decode engine a
        prefill returns (1, 1, V): the prompt's last row, the one the host
        reads."""
        b, s = input_ids.shape
        # lookup_table squeezes a (B, 1) id column: restore (B, S, h)
        x = dispatch_op('reshape', {'x': self.embed(input_ids)},
                        {'shape': [b, s, self.cfg.hidden_size]})
        for block in self.layers:
            x = block(x, cache)
        if cache is not None:
            x = Tensor(_scored_rows(cache, x.value, 1), stop_gradient=True)
        return dispatch_op('lm_head', {'x': self.norm_f(x),
                                       'w': self.head.weight}, {})
