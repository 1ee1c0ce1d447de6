"""Decoder-only LM of the `deepseek_v3` family (kanana-2-30b-a3b,
DeepSeek-V3, Moonlight ...): pre-norm RMSNorm blocks, multi-head latent
attention (MLA) with no query low-rank, a dense gated feed-forward in the
first ``first_k_dense_replace`` layers and sigmoid-routed experts with shared
experts after them, an untied head, parameters kept in ``dtype``.

    x += Attn(norm1(x)); x += FFN(norm2(x)); logits = head(final_norm(x))

The forward contract is models/causal_lm.py's: ``model(ids, pos_ids=None,
cache=None)``. Whole-sequence (``cache=None``) attends in the expanded form.
Under the decode engine, prefill attends in the expanded form over the
prompt and writes each token's latent row ``[c | k_rope]`` (after the norm,
after RoPE) into the paged pool (`CacheContext.attend_latent`); decode
steps read the pool in the absorbed form. Both are the same function of the
weights (ops/llm_ops.py).

The configuration takes the keys of the published `config.json` under their
own names and refuses a value it has no equations for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..dygraph import Embedding, Layer, LayerList, Linear
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import ConstantInitializer, NormalInitializer
from ..param_attr import ParamAttr

# what the block's equations assume of the published keys they do not read
_ONLY = {'q_lora_rank': None, 'n_group': 1, 'topk_group': 1,
         'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc',
         'rope_scaling': None, 'rope_interleave': True,
         'attention_bias': False, 'tie_word_embeddings': False,
         'hidden_act': 'silu', 'moe_layer_freq': 1}
# published keys that repeat another or describe nothing of the forward
_IGNORED = ('head_dim', 'model_type', 'qk_head_dim', 'num_key_value_heads')


def check_published(owner, published, only, ignored):
    """Refuse a published key ``owner`` has no equations for: one it does
    not know, or a value other than the one its block assumes (``only``);
    keys in ``ignored`` repeat another or describe nothing of the forward."""
    for key, value in published.items():
        if key in ignored:
            continue
        if key not in only:
            raise ValueError(f'{owner}: unknown key {key!r}')
        if value != only[key]:
            raise ValueError(
                f'{owner}: {key}={value!r} is not supported (only '
                f'{only[key]!r}): the block has no equations for it')


def from_published(cls, published, extras, only, ignored):
    """``cls`` from a dict that holds the published `config.json` keys among
    others (a benchmark configuration file): the keys ``cls`` knows are
    taken, under their own names, and ``extras`` beside them."""
    import inspect
    known = {name for name, p in inspect.signature(
        cls.__init__).parameters.items()
        if p.kind is p.POSITIONAL_OR_KEYWORD} | set(only) | set(ignored)
    return cls(**{**{k: v for k, v in published.items() if k in known},
                  **extras})


class LatentMoEConfig:
    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers,
                 num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, n_routed_experts,
                 n_shared_experts, num_experts_per_tok,
                 first_k_dense_replace=1, routed_scaling_factor=1.0,
                 norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
                 max_position_embeddings=4096, initializer_range=0.02,
                 router_bias_scale=0.0, dtype='float32', **published):
        check_published('LatentMoEConfig', published, _ONLY, _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # e_score_correction_bias is zero in a fresh checkpoint; a seeded
        # N(0, scale) tells choosing by s + b from weighting by s
        self.router_bias_scale = float(router_bias_scale)
        self.dtype = dtype

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    @property
    def latent_row_width(self):
        """Values cached per token per layer: [c | k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**overrides):
        """Test scale: one dense and two expert layers, 8 experts top-2."""
        sizes = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                     moe_intermediate_size=32, num_hidden_layers=3,
                     num_attention_heads=2, kv_lora_rank=16,
                     qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                     n_routed_experts=8, n_shared_experts=2,
                     num_experts_per_tok=2, routed_scaling_factor=2.448,
                     rope_theta=1e6, max_position_embeddings=64,
                     initializer_range=0.2, router_bias_scale=0.05)
        sizes.update(overrides)
        return LatentMoEConfig(**sizes)


def _linear(cfg, n_in, n_out):
    return Linear(n_in, n_out, bias_attr=False, dtype=cfg.dtype,
                  param_attr=ParamAttr(initializer=NormalInitializer(
                      0.0, cfg.initializer_range)))


class RMSNorm(Layer):
    def __init__(self, cfg, width):
        super().__init__()
        self.weight = self.create_parameter(
            [width], None, cfg.dtype,
            default_initializer=ConstantInitializer(1.0))
        self._eps = cfg.rms_norm_eps

    def forward(self, x):
        return dispatch_op('rms_norm', {'x': x, 'scale': self.weight},
                           {'epsilon': self._eps})


class Relu2FFN(Layer):
    """The non-gated feed-forward `w_down(relu(x · w_up)²)` (`nemotron_h`'s
    shared expert)."""

    def __init__(self, cfg, width):
        super().__init__()
        self.up = _linear(cfg, cfg.hidden_size, width)
        self.down = _linear(cfg, width, cfg.hidden_size)

    def forward(self, x):
        return dispatch_op('relu2_ffn', {'x': x, 'w_up': self.up.weight,
                                         'w_down': self.down.weight}, {})


class GatedFFN(Layer):
    def __init__(self, cfg, width):
        super().__init__()
        self.gate = _linear(cfg, cfg.hidden_size, width)
        self.up = _linear(cfg, cfg.hidden_size, width)
        self.down = _linear(cfg, width, cfg.hidden_size)

    def forward(self, x):
        return dispatch_op('swiglu_ffn', {
            'x': x, 'w_gate': self.gate.weight, 'w_up': self.up.weight,
            'w_down': self.down.weight}, {})


class RoutedExperts(Layer):
    """``n_routed_experts`` gated feed-forwards behind a router, plus one
    shared feed-forward of ``n_shared_experts`` expert widths (none where
    that is 0). The router scores by ``cfg.scoring_func``: 'sigmoid' with a
    selection bias (the default: `deepseek_v3`) or 'softmax' with none
    (`sdar_moe`, models/block_diffusion_lm.py).

    ``cfg.experts_held`` = (first, count) makes the layer one chip's SHARE
    of a layer whose experts are spread over several (models/
    sliding_moe_lm.py): the router keeps its ``n_routed_experts`` outputs
    and its top-k, the layer holds the weights of experts [first, first +
    count) alone, and it returns Shared(x) + the part of the routed sum
    that its own experts give. The shares of a layer, with the shared
    expert counted once, add up to the whole layer. Nothing here stands in
    for the other chips or their exchange. None (the default): every
    expert is held, and the layer is what it was.

    ``cfg.moe_latent_size`` = ℓ makes the experts LATENT (`nemotron_h`):
    the router scores the full-width x, the experts run on W_↓ x (h -> ℓ)
    and their weighted sum comes back through W_↑ (ℓ -> h), under the scope
    `moe/latent`; the shared expert stays at full width.
    ``cfg.expert_activation`` = 'relu2' makes every expert, the shared one
    too, the non-gated `w_down(relu(x · w_up)²)` (no gate weights), the
    shared one ``cfg.shared_expert_intermediate_size`` wide. Neither set
    (the default): gated silu experts at full width."""

    def __init__(self, cfg):
        super().__init__()
        e, h, f = (cfg.n_routed_experts, cfg.hidden_size,
                   cfg.moe_intermediate_size)
        self.held = getattr(cfg, 'experts_held', None)
        held = e if self.held is None else self.held[1]
        normal = NormalInitializer(0.0, cfg.initializer_range)
        scoring = getattr(cfg, 'scoring_func', 'sigmoid')
        self.router = _linear(cfg, h, e)
        self.router_bias = None
        if scoring == 'sigmoid':
            self.router_bias = self.create_parameter(
                [e], None, 'float32', default_initializer=NormalInitializer(
                    0.0, cfg.router_bias_scale))
        latent = getattr(cfg, 'moe_latent_size', None)
        self.latent = bool(latent)
        if latent:
            self.latent_down = _linear(cfg, h, latent)
            self.latent_up = _linear(cfg, latent, h)
        width = latent or h         # what an expert reads and returns
        self.gated = getattr(cfg, 'expert_activation', 'silu') == 'silu'
        self.experts_gate = self.create_parameter(
            [held, width, f], None, cfg.dtype,
            default_initializer=normal) if self.gated else None
        self.experts_up = self.create_parameter(
            [held, width, f], None, cfg.dtype, default_initializer=normal)
        self.experts_down = self.create_parameter(
            [held, f, width], None, cfg.dtype, default_initializer=normal)
        if not self.gated:
            self.shared = Relu2FFN(cfg, cfg.shared_expert_intermediate_size)
        else:
            self.shared = GatedFFN(cfg, cfg.n_shared_experts * f) \
                if cfg.n_shared_experts else None
        self._route = {'top_k': cfg.num_experts_per_tok,
                       'routed_scaling_factor': cfg.routed_scaling_factor,
                       'norm_topk_prob': cfg.norm_topk_prob}
        if scoring != 'sigmoid':    # the sigmoid dispatch stays as it was
            self._route['scoring_func'] = scoring
        self._experts = {} if self.held is None \
            else {'experts_held': self.held}
        if not self.gated:
            self._experts['activation'] = 'relu2'
        # what the normaliser adds to the chosen scores' sum: a family that
        # says otherwise than the op's 1e-20 names it (`lfm2_moe`: 1e-6)
        if hasattr(cfg, 'router_norm_epsilon'):
            self._route['norm_epsilon'] = cfg.router_norm_epsilon
        # whose choices a prefill notes for the host: the scored row's
        # alone, or every row's where a later row READS its neighbours'
        # values directly (a short convolution: models/hybrid_conv_moe_lm.py)
        self.note_every_row = getattr(cfg, 'note_every_rows_experts', False)

    def forward(self, x, cache=None):
        b, s, h = x.shape
        flat = dispatch_op('reshape', {'x': x}, {'shape': [b * s, h]})
        # the scopes name the device ops of each part in a profiler trace
        with jax.named_scope('moe/route'):
            router = {'x': flat, 'w_gate': self.router.weight}
            if self.router_bias is not None:
                router['bias'] = self.router_bias
            ids, weights = dispatch_op('moe_router', router, self._route)
        inner = flat
        if self.latent:
            with jax.named_scope('moe/latent'):
                inner = self.latent_down(flat)
        with jax.named_scope('moe/experts'):
            routed, counts = dispatch_op('moe_experts', {
                'x': inner, 'ids': ids, 'weights': weights,
                'w_gate': self.experts_gate, 'w_up': self.experts_up,
                'w_down': self.experts_down}, self._experts)
        if self.latent:
            with jax.named_scope('moe/latent'):
                routed = self.latent_up(routed)
        if cache is not None:
            # for the host's counters: the rows each expert was given of the
            # call's LIVE tokens (the work the mathematics needs; `counts`
            # holds a rung's padding and idle slots too), and the experts
            # behind the rows the host reads
            live = cache.live_rows(b * s)
            first = 0 if self.held is None else self.held[0]
            chosen = (ids.value - first)[..., None] == jnp.arange(
                counts.shape[0], dtype=jnp.int32)
            cache.note('expert_counts', (chosen & live[:, None, None]).sum(
                (0, 1), dtype=jnp.int32))
            if self.held is not None:
                # all the live rows' assignments, held here or elsewhere
                cache.note('expert_assignments', live.sum(
                    dtype=jnp.int32) * ids.shape[-1])
            cache.note('expert_ids', ids.value if self.note_every_row
                       else _scored_rows(cache, ids.value, 0))
        if self.shared is not None:
            with jax.named_scope('moe/shared'):
                routed = routed + self.shared(flat)
        return dispatch_op('reshape', {'x': routed}, {'shape': [b, s, h]})


def _scored_rows(cache, x, axis):
    """The rows of ``x`` along ``axis`` whose logits the host reads: in a
    prefill the prompt's last row alone (`cache.last`), so that a large head
    does not score a whole rung for one row; in a decode step all of them."""
    if cache.mode != 'prefill':
        return x
    return jax.lax.dynamic_slice_in_dim(x, cache.last, 1, axis)


class LatentAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        heads = cfg.num_attention_heads
        self.q_proj = _linear(cfg, cfg.hidden_size, heads * (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
        self.kv_a_proj = _linear(cfg, cfg.hidden_size, cfg.latent_row_width)
        self.kv_a_norm = RMSNorm(cfg, cfg.kv_lora_rank)
        self.kv_b_proj = _linear(cfg, cfg.kv_lora_rank, heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _linear(cfg, heads * cfg.v_head_dim, cfg.hidden_size)
        self._attend = {
            'qk_nope_dim': cfg.qk_nope_head_dim, 'v_dim': cfg.v_head_dim,
            'sm_scale': (cfg.qk_nope_head_dim
                         + cfg.qk_rope_head_dim) ** -0.5}

    def forward(self, x, pos_ids, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        q = dispatch_op('reshape', {'x': self.q_proj(x)}, {'shape': [
            b, s, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim]})
        q = dispatch_op('rope', {'x': q, 'pos': pos_ids}, {
            'theta': cfg.rope_theta, 'nope_dim': cfg.qk_nope_head_dim})
        c, k_rope = dispatch_op('split', {'x': self.kv_a_proj(x)}, {
            'num_or_sections': [cfg.kv_lora_rank, cfg.qk_rope_head_dim],
            'dim': -1})
        k_rope = dispatch_op('rope', {'x': k_rope, 'pos': pos_ids},
                             {'theta': cfg.rope_theta})
        latent = dispatch_op('concat', {'xs': [self.kv_a_norm(c), k_rope]},
                             {'axis': -1})
        inputs = {'q': q, 'latent': latent, 'w_kvb': self.kv_b_proj.weight}
        if cache is None:
            with jax.named_scope('mla/prefill_attention'):
                out = dispatch_op('mla_prefill_attention', inputs,
                                  self._attend)
        else:
            out = cache.attend_latent(inputs, self._attend)
        return self.o_proj(out)


class LatentMoEBlock(Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.norm1 = RMSNorm(cfg, cfg.hidden_size)
        self.attn = LatentAttention(cfg)
        self.norm2 = RMSNorm(cfg, cfg.hidden_size)
        self.routed = index >= cfg.first_k_dense_replace
        self.ffn = RoutedExperts(cfg) if self.routed \
            else GatedFFN(cfg, cfg.intermediate_size)

    def forward(self, x, pos_ids, cache=None):
        x = x + self.attn(self.norm1(x), pos_ids, cache)
        h = self.norm2(x)
        return x + (self.ffn(h, cache) if self.routed else self.ffn(h))


class LatentMoELM(Layer):
    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([LatentMoEBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.final_norm = RMSNorm(cfg, cfg.hidden_size)
        self.head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def cache_layout(self):
        """What the decode engine caches of this model: one latent row per
        token per layer (serving/decode/layout.py)."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        return CacheLayout((LayerCache.latent(self.cfg.latent_row_width),)
                           * self.cfg.num_hidden_layers)

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
        return dispatch_op('lm_head', {'x': self.final_norm(x),
                                       'w': self.head.weight}, {})
