"""Decoder-only LM of the `brumby` family (manifestai/Brumby-14B-Base): the
Qwen3-shaped dense decoder with every attention layer replaced by a power
retention layer: pre-norm RMSNorm blocks, grouped key/value heads, per-head
RMSNorm on q and k before RoPE, a gated feed-forward, an untied head,
parameters kept in ``dtype``.

    x += Ret(norm1(x)); x += FFN(norm2(x)); logits = head(final_norm(x))

``Ret`` is degree-2 power retention with one sigmoid gate per key/value head
(ops/llm_ops.py): position i weighs (q_t·k_i)² Π_{s=i+1..t} γ_s for query t,
normalised by the sum of the weights. It is a linear-attention layer: per
request and per key/value head a state of d(d+1)/2 × (d + 1) float32 values,
whatever the context, and no row per token.

The forward contract is models/causal_lm.py's: ``model(ids, pos_ids=None,
cache=None)``. Whole-sequence (``cache=None``) runs the chunked scan and
drops the final state. Under the decode engine a prefill runs the same scan
over the bucket and leaves the final state in the request's row of the state
cache (`CacheContext.attend_retention`); a decode step advances every slot's
state by one token and reads it.

The configuration takes the keys of the published `config.json` under their
own names and refuses a value it has no equations for. What `config.json`
does not carry (degree 2, the gate, the normaliser, QK-norm, the chunk) is
the benchmark configuration's `assumed` (benchmark/configs/brumby_14b.json).
"""
from __future__ import annotations

import jax
import numpy as np

from ..dygraph import Embedding, Layer, LayerList
from ..dygraph.tape import Tensor, dispatch_op
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .latent_moe_lm import (GatedFFN, RMSNorm, _linear, _scored_rows,
                            check_published, from_published)

# what the block's equations assume of the published keys they do not read
_ONLY = {'sliding_window': None, 'use_sliding_window': False,
         'rope_scaling': None, 'attention_bias': False,
         'tie_word_embeddings': False, 'hidden_act': 'silu'}
# published keys that describe nothing of the forward: `max_window_layers`
# counts layers of a window that `use_sliding_window: false` switches off
_IGNORED = ('model_type', 'max_window_layers')


class RetentionLMConfig:
    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, head_dim, rms_norm_eps=1e-6,
                 rope_theta=10000.0, max_position_embeddings=4096,
                 initializer_range=0.02, gate_shift=0.0, prefill_chunk=256,
                 dtype='float32', **published):
        check_published('RetentionLMConfig', published, _ONLY, _IGNORED)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f'RetentionLMConfig: {self.num_attention_heads} query heads '
                f'do not divide over {self.num_key_value_heads} key/value '
                f'heads')
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # γ = sigmoid(h·W_g + gate_shift): 0 is the form assumed for the
        # published model; random weights need about +6 to gate as a
        # trained model does (near 1), so that an old state matters
        self.gate_shift = float(gate_shift)
        self.prefill_chunk = int(prefill_chunk)
        self.dtype = dtype

    @classmethod
    def from_published(cls, published, **extras):
        """From a dict that holds the published `config.json` keys among
        others (a benchmark configuration file): the keys this class knows
        are taken, under their own names, and ``extras`` beside them."""
        return from_published(cls, published, extras, _ONLY, _IGNORED)

    @staticmethod
    def tiny(**overrides):
        """Test scale: three layers, 6 query heads over 2 key/value heads of
        8, a chunk of 4."""
        sizes = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=3, num_attention_heads=6,
                     num_key_value_heads=2, head_dim=8, rope_theta=1e6,
                     max_position_embeddings=64, initializer_range=0.2,
                     gate_shift=3.0, prefill_chunk=4)
        sizes.update(overrides)
        return RetentionLMConfig(**sizes)


class PowerRetention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        heads, groups, d = (cfg.num_attention_heads,
                            cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = _linear(cfg, cfg.hidden_size, heads * d)
        self.k_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.v_proj = _linear(cfg, cfg.hidden_size, groups * d)
        self.o_proj = _linear(cfg, heads * d, cfg.hidden_size)
        self.gate = _linear(cfg, cfg.hidden_size, groups)
        self.q_norm = RMSNorm(cfg, d)
        self.k_norm = RMSNorm(cfg, d)
        self._scan = {'chunk': cfg.prefill_chunk}

    def _heads(self, x, n, b, s):
        return dispatch_op('reshape', {'x': x},
                           {'shape': [b, s, n, self.cfg.head_dim]})

    def forward(self, x, pos_ids, cache=None):
        cfg = self.cfg
        b, s, _ = x.shape
        rope = {'theta': cfg.rope_theta}
        q = self._heads(self.q_proj(x), cfg.num_attention_heads, b, s)
        k = self._heads(self.k_proj(x), cfg.num_key_value_heads, b, s)
        inputs = {
            'q': dispatch_op('rope', {'x': self.q_norm(q), 'pos': pos_ids},
                             rope),
            'k': dispatch_op('rope', {'x': self.k_norm(k), 'pos': pos_ids},
                             rope),
            'v': self._heads(self.v_proj(x), cfg.num_key_value_heads, b, s),
            'log_gate': dispatch_op(
                'retention_gate', {'x': x, 'w': self.gate.weight},
                {'shift': cfg.gate_shift})}
        if cache is None:
            with jax.named_scope('retention/prefill_scan'):
                out, _ = dispatch_op('power_retention_prefill', inputs,
                                     self._scan)
        else:
            out = cache.attend_retention(inputs, self._scan)
        return self.o_proj(out)


class RetentionBlock(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.norm1 = RMSNorm(cfg, cfg.hidden_size)
        self.attn = PowerRetention(cfg)
        self.norm2 = RMSNorm(cfg, cfg.hidden_size)
        self.ffn = GatedFFN(cfg, cfg.intermediate_size)

    def forward(self, x, pos_ids, cache=None):
        x = x + self.attn(self.norm1(x), pos_ids, cache)
        return x + self.ffn(self.norm2(x))


class RetentionLM(Layer):
    def __init__(self, cfg: RetentionLMConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(initializer=NormalInitializer(
                0.0, cfg.initializer_range)))
        self.layers = LayerList([RetentionBlock(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.final_norm = RMSNorm(cfg, cfg.hidden_size)
        self.head = _linear(cfg, cfg.hidden_size, cfg.vocab_size)

    def cache_layout(self):
        """What the decode engine caches of this model: per REQUEST per
        layer one recurrent state, key/value heads' blocks of
        (ops/llm_ops.py::retention_state_rows) × ``head_dim`` float32
        values, and no row per token (serving/decode/layout.py)."""
        from ..ops.llm_ops import retention_state_rows
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        block = (cfg.num_key_value_heads,
                 retention_state_rows(cfg.head_dim)[2], cfg.head_dim)
        return CacheLayout((LayerCache.state(block, 'retention'),)
                           * cfg.num_hidden_layers)

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
