"""Decoder-only LM of the `sdar_moe` family (JetLM/SDAR-30B-A3B-Chat): the
Qwen3-MoE-shaped decoder, generating by BLOCK DIFFUSION: pre-norm RMSNorm
blocks, grouped key/value heads, per-head RMSNorm on q and k before RoPE,
softmax-routed experts in every layer (no dense layer, no shared expert), an
untied head, parameters kept in ``dtype``.

    x += Attn(norm1(x)); x += Experts(norm2(x)); logits = head(final_norm(x))

What sets the family apart is the mask and how it generates. Key j is
visible to query i iff j // B <= i // B (B = ``block_length``): causal across
blocks, bidirectional inside one, in the prompt and in the answer alike. An
answer is made a block at a time: the block starts as B `MASK` tokens (a
prompt's last P mod B tokens open the first block, already fixed); a
DENOISING forward feeds the block's B tokens over the cache and yields B
logits rows, row i predicting position i itself (no shift); the masked
positions the model is most confident of are unmasked, a few a forward;
when none is masked a COMMIT forward feeds the finished block once more and
only then are its K/V kept (serving/decode/engine.py::window_step, the
schedule in serving/decode/diffusion.py).

The forward contract is models/causal_lm.py's: ``model(ids, pos_ids=None,
cache=None)``. Whole-sequence (``cache=None``) attends under the block mask.
Under the decode engine the model is a WINDOW model (its layout's ``window``
= B):
a prefill writes the K/V of the prompt's whole blocks and scores nothing
(it returns None: the first block's first forward reads the prompt's tail
beside its masks), and a step feeds B rows a slot, every row at the extent
context + B (`CacheContext.attend(block_len=B)`), and returns (S, B, V).

The configuration takes the keys of the published `config.json` under their
own names and refuses a value it has no equations for. What `config.json`
does not carry (the block length, the `MASK` id, QK-norm, the schedule) is
the benchmark configuration's `assumed` (benchmark/configs/sdar_30b_a3b.json).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..dygraph import Embedding, Layer, LayerList
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .latent_moe_lm import (RMSNorm, RoutedExperts, _linear, check_published,
                            from_published)

# what the block's equations assume of the published keys they do not read
_ONLY = {'sliding_window': None, 'use_sliding_window': False,
         'rope_scaling': None, 'attention_bias': False,
         'tie_word_embeddings': False, 'hidden_act': 'silu',
         'decoder_sparse_step': 1, 'mlp_only_layers': []}
# published keys that describe nothing of the forward: `max_window_layers`
# counts layers of a window that `use_sliding_window: false` switches off;
# `intermediate_size` is the width of the dense layers `mlp_only_layers: []`
# and `decoder_sparse_step: 1` say there are none of
_IGNORED = ('model_type', 'max_window_layers', 'intermediate_size')


class BlockDiffusionMoEConfig:
    def __init__(self, vocab_size, hidden_size, moe_intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, head_dim, num_experts,
                 num_experts_per_tok, norm_topk_prob=True, rms_norm_eps=1e-6,
                 rope_theta=10000.0, max_position_embeddings=4096,
                 initializer_range=0.02, block_length=4, mask_token_id=None,
                 dtype='float32', **published):
        check_published('BlockDiffusionMoEConfig', published, _ONLY,
                        _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'BlockDiffusionMoEConfig: {self.num_attention_heads} query '
                f'heads do not divide over {self.num_key_value_heads} '
                f'key/value heads')
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.block_length = int(block_length)
        if self.block_length < 2:
            raise ValueError(
                f'BlockDiffusionMoEConfig: block_length={block_length}: a '
                f'block holds at least two positions (one is plain '
                f'next-token decoding, which this block has no shift for)')
        # the id a masked position is fed as, never an answer: the last of
        # the vocabulary where the configuration names none
        self.mask_token_id = int(self.vocab_size - 1 if mask_token_id is None
                                 else mask_token_id)
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f'BlockDiffusionMoEConfig: mask_token_id='
                f'{self.mask_token_id} is no row of a vocabulary of '
                f'{self.vocab_size}')
        self.dtype = dtype
        # `RoutedExperts` (models/latent_moe_lm.py) under its own names: a
        # softmax router with no selection bias, no shared expert, weights
        # normalised over the chosen and not scaled
        self.n_routed_experts = self.num_experts
        self.n_shared_experts = 0
        self.scoring_func = 'softmax'
        self.routed_scaling_factor = 1.0

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    @staticmethod
    def tiny(**overrides):
        """Test scale: three layers, 8 query heads over 2 key/value heads of
        8, 8 experts top-2, blocks of 4."""
        sizes = dict(vocab_size=96, hidden_size=32, moe_intermediate_size=32,
                     num_hidden_layers=3, num_attention_heads=8,
                     num_key_value_heads=2, head_dim=8, num_experts=8,
                     num_experts_per_tok=2, rope_theta=1e6,
                     max_position_embeddings=128, initializer_range=0.2,
                     block_length=4, mask_token_id=95)
        sizes.update(overrides)
        return BlockDiffusionMoEConfig(**sizes)


def block_mask_bias(length, block_length, dtype=jnp.float32):
    """(length, length) additive mask: 0 where key j is visible to row i
    (j // B <= i // B), the dtype's most negative value elsewhere."""
    block_of = np.arange(length) // int(block_length)
    seen = block_of[None, :] <= block_of[:, None]
    return jnp.where(jnp.asarray(seen), 0.0, jnp.finfo(dtype).min
                     ).astype(dtype)


class BlockAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = _linear(cfg, cfg.hidden_size, heads * d)
        self.k_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.v_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.o_proj = _linear(cfg, heads * d, cfg.hidden_size)
        self.q_norm = RMSNorm(cfg, d)
        self.k_norm = RMSNorm(cfg, d)

    def _heads(self, x, n, b, s):
        return dispatch_op('reshape', {'x': x},
                           {'shape': [b, s, n, self.cfg.head_dim]})

    @staticmethod
    def _head_major(x):
        return dispatch_op('transpose', {'x': x}, {'perm': [0, 2, 1, 3]})

    def _repeat(self, x, rep, b, s):
        """(B, G, S, d) -> (B, G·rep, S, d), head g·rep + r a copy of g."""
        groups, d = self.cfg.num_key_value_heads, self.cfg.head_dim
        x = dispatch_op('reshape', {'x': x},
                        {'shape': [b, groups, 1, s, d]})
        x = dispatch_op('expand', {'x': x},
                        {'expand_times': [1, 1, rep, 1, 1]})
        return dispatch_op('reshape', {'x': x},
                           {'shape': [b, groups * rep, s, d]})

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
            out = cache.attend(q, k, v, sm_scale=scale,
                               block_len=cfg.block_length)
        else:
            # every query head its own copy of its key/value head, and the
            # block mask as a bias: the plain form the paged reads are held
            # to (tests/framework/test_block_diffusion.py)
            rep = heads // groups
            if rep > 1:
                k, v = (self._repeat(t, rep, b, s) for t in (k, v))
            out = dispatch_op('fused_attention', {
                'q': q, 'k': k, 'v': v,
                'bias': block_mask_bias(s, cfg.block_length)},
                {'sm_scale': scale})
        out = dispatch_op('reshape', {'x': self._head_major(out)},
                          {'shape': [b, s, heads * d]})
        return self.o_proj(out)


class BlockDiffusionBlock(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.norm1 = RMSNorm(cfg, cfg.hidden_size)
        self.attn = BlockAttention(cfg)
        self.norm2 = RMSNorm(cfg, cfg.hidden_size)
        self.ffn = RoutedExperts(cfg)

    def forward(self, x, pos_ids, cache=None):
        x = x + self.attn(self.norm1(x), pos_ids, cache)
        return x + self.ffn(self.norm2(x), cache)


class BlockDiffusionMoELM(Layer):
    def __init__(self, cfg: BlockDiffusionMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([BlockDiffusionBlock(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.final_norm = RMSNorm(cfg, cfg.hidden_size)
        self.head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    @property
    def mask_token_id(self):
        return self.cfg.mask_token_id

    def cache_layout(self):
        """What the decode engine caches of this model: K and V rows of the
        KEY/VALUE heads per token per layer, read a block at a time, and
        the window a step feeds: one whole block of rows a slot
        (serving/decode/layout.py)."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        return CacheLayout((LayerCache.kv(cfg.num_key_value_heads,
                                          cfg.head_dim, read='window'),)
                           * cfg.num_hidden_layers, window=cfg.block_length)

    def forward(self, input_ids, pos_ids=None, cache=None):
        """``input_ids`` (B, S) -> float32 logits (B, S, V), row i the
        distribution of position i itself; ``pos_ids`` (B, S) defaults to
        0..S-1 per row. Under the decode engine a prefill returns None: it
        writes the prompt's K/V and nothing of it is scored."""
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
        if cache is not None and cache.mode == 'prefill':
            return None
        return dispatch_op('lm_head', {'x': self.final_norm(x),
                                       'w': self.head.weight}, {})
