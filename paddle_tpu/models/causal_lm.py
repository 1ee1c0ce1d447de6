"""Decoder-only causal transformer LM — the workload the stateful decode
engine (serving/decode/, docs/SERVING.md "Stateful decode") serves.

Built from the BERT building blocks (models/bert.py TransformerLayer /
MultiHeadAttention) with a causal mask and a weight-tied LM head, so the
incremental-decode cache path added to MultiHeadAttention is exercised by a
real model rather than a bespoke one. Two execution modes share every
parameter and the same arithmetic:

- **whole-sequence** (``cache=None``): the full (B, L) padded sequence in
  one forward — training, and the uncached reference that
  :func:`greedy_generate` uses;
- **incremental** (``cache=`` a serving/decode CacheContext): prefill
  writes the prompt's K/V into paged cache blocks, decode steps run at
  fixed (S, 1) shape reading K/V through per-slot block tables. The decode
  engine traces this mode once per shape into one jitted XLA program
  (serving/decode/engine.py), so ``cache`` and every tensor here may be a
  tracer.

Parity contract (the decode engine's acceptance bar, ROADMAP D1's rule):
the engine's greedy token stream equals :func:`greedy_generate`'s at
``pad_len == engine.padded_context``, and a decode step's logits row
agrees with the matching row of a whole-sequence forward padded to the
SAME context extent within a stated tolerance — one fused program and
~300 eager kernels round differently (one ulp seen on CPU: 1.2e-7 at a
scale of 0.47; tests/framework/test_decode_fused_programs.py). Both paths
keep the unfused matmul attention formulation over the same padded extent
— XLA CPU keeps matmul rows stable across the sequence extent, while the
einsum in fused_attention's fallback does not (measured; see
ops/nn_ops.py:paged_attention) — so ``use_fused_attention`` defaults off
here.
"""
from __future__ import annotations

import numpy as np

from ..dygraph import Layer, Embedding, LayerNorm, Dropout, LayerList
from ..dygraph.tape import Tensor, dispatch_op, no_grad_guard
from .bert import TransformerLayer, _init


class CausalLMConfig:
    """Duck-types the BertConfig fields TransformerLayer reads, plus LM
    bits. ``attention_probs_dropout_prob`` is pinned to 0 (the fused and
    cached attention paths both skip attention-prob dropout)."""

    def __init__(self, vocab_size=32000, hidden_size=512,
                 num_hidden_layers=6, num_attention_heads=8,
                 intermediate_size=2048, hidden_act='gelu',
                 hidden_dropout_prob=0.1, max_position_embeddings=512,
                 initializer_range=0.02, use_fused_attention=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = 0.0
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.use_fused_attention = use_fused_attention

    @staticmethod
    def tiny():
        """Test/bench scale."""
        return CausalLMConfig(vocab_size=128, hidden_size=32,
                              num_hidden_layers=2, num_attention_heads=2,
                              intermediate_size=64,
                              max_position_embeddings=128)


class TransformerLM(Layer):
    def __init__(self, cfg: CausalLMConfig):
        super().__init__()
        self.cfg = cfg
        self.word_emb = Embedding([cfg.vocab_size, cfg.hidden_size],
                                  param_attr=_init(cfg))
        self.pos_emb = Embedding([cfg.max_position_embeddings,
                                  cfg.hidden_size], param_attr=_init(cfg))
        self.emb_ln = LayerNorm(cfg.hidden_size)
        self.emb_drop = Dropout(cfg.hidden_dropout_prob,
                                dropout_implementation='upscale_in_train')
        self.blocks = LayerList([TransformerLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])

    @property
    def num_cache_layers(self):
        return self.cfg.num_hidden_layers

    def cache_layout(self):
        """What the decode engine caches of this model: K and V rows of
        every head per token per layer, read over the live blocks
        (serving/decode/layout.py)."""
        from ..serving.decode.layout import CacheLayout, LayerCache
        cfg = self.cfg
        heads = cfg.num_attention_heads
        return CacheLayout((LayerCache.kv(heads, cfg.hidden_size // heads),)
                           * cfg.num_hidden_layers)

    def forward(self, input_ids, pos_ids=None, cache=None):
        """``input_ids`` (B, S) → logits (B, S, V). ``pos_ids`` defaults to
        0..S-1 per row; the decode engine passes each slot's context
        position explicitly. ``cache`` routes attention through the paged
        KV cache (see module docstring)."""
        b, s = input_ids.shape
        if pos_ids is None:
            pos_ids = Tensor(
                np.arange(s, dtype=np.int64)[None, :].repeat(b, 0),
                stop_gradient=True)
        x = self.word_emb(input_ids) + self.pos_emb(pos_ids)
        # lookup_table squeezes (B, 1) id columns (LoD convention) — the
        # decode step feeds exactly that shape; restore (B, S, H)
        x = dispatch_op('reshape', {'x': x},
                        {'shape': [b, s, self.cfg.hidden_size]})
        x = self.emb_drop(self.emb_ln(x))
        for blk in self.blocks:
            x = blk(x, None, causal=True, cache=cache)
        # weight-tied LM head (same matrix as word_emb, transposed)
        return dispatch_op('matmul', {'x': x, 'y': self.word_emb.weight},
                           {'transpose_y': True})


def lm_loss(logits, labels, pad_id=0):
    """Next-token CE: logits (B, S, V) vs labels (B, S) shifted left by the
    caller; pad positions masked out (same scheme as transformer_loss)."""
    V = logits.shape[-1]
    flat = dispatch_op('reshape', {'x': logits}, {'shape': [-1, V]})
    lbl = dispatch_op('reshape', {'x': labels}, {'shape': [-1, 1]})
    raw, _ = dispatch_op('softmax_with_cross_entropy',
                         {'logits': flat, 'label': lbl}, {})
    mask = dispatch_op('cast', {'x': dispatch_op(
        'not_equal', {'x': lbl,
                      'y': Tensor(np.array([pad_id], np.int64),
                                  stop_gradient=True)}, {})},
        {'dtype': 'float32'})
    raw = dispatch_op('reshape', {'x': raw}, {'shape': [-1, 1]}) * mask
    total = dispatch_op('reduce_sum', {'x': raw}, {})
    denom = dispatch_op('reduce_sum', {'x': mask}, {})
    return total / (denom + 1e-9)


def greedy_generate(model, prompt_ids, max_new_tokens, eos_id=None,
                    pad_len=None):
    """Uncached whole-sequence greedy decode at ONE fixed padded shape.

    Every step re-runs the full (1, pad_len) sequence and reads the logits
    row of the last real position — O(L²) work, but a single compile for
    the whole generation (the fixed-shape discipline that also fixed
    models/transformer.py's decode retracing). This is the REFERENCE the
    decode engine is tested against: run it with
    ``pad_len == engine.padded_context`` and the streamed tokens must be
    identical (tests/framework/test_decode_engine.py asserts it on every
    request).

    Returns the generated token ids (list, ≤ max_new_tokens; stops at
    ``eos_id``).
    """
    prompt = [int(t) for t in prompt_ids]
    P = len(prompt)
    if P < 1:
        raise ValueError('empty prompt')
    L = int(pad_len) if pad_len else P + int(max_new_tokens)
    if L < P + int(max_new_tokens):
        raise ValueError(
            f'pad_len={L} cannot hold prompt({P}) + {max_new_tokens} new '
            f'tokens')
    buf = np.zeros((1, L), np.int64)
    buf[0, :P] = prompt
    out = []
    with no_grad_guard():
        for i in range(int(max_new_tokens)):
            c = P + i
            logits = model(Tensor(buf, stop_gradient=True))
            nxt = int(np.asarray(logits.numpy())[0, c - 1].argmax())
            out.append(nxt)
            buf[0, c] = nxt
            if eos_id is not None and nxt == int(eos_id):
                break
    return out


def sampled_generate(model, prompt_ids, max_new_tokens, sampler, eos_id=None,
                     pad_len=None):
    """Uncached whole-sequence SAMPLED decode — :func:`greedy_generate`'s
    loop with the argmax replaced by ``sampler(row, index)``, where ``row``
    is the float logits row of the last real position and ``index`` the
    0-based generated-token index. Pair it with a
    ``serving.decode.TokenSampler`` bound to the same request_id/params and
    ``pad_len == engine.padded_context`` to get the replay
    reference for the engine's sampled path (the per-token fold_in key
    depends only on (seed, index), so cached and uncached loops draw the
    same stream).

    Returns the generated token ids (list, ≤ max_new_tokens; stops at
    ``eos_id``).
    """
    prompt = [int(t) for t in prompt_ids]
    P = len(prompt)
    if P < 1:
        raise ValueError('empty prompt')
    L = int(pad_len) if pad_len else P + int(max_new_tokens)
    if L < P + int(max_new_tokens):
        raise ValueError(
            f'pad_len={L} cannot hold prompt({P}) + {max_new_tokens} new '
            f'tokens')
    buf = np.zeros((1, L), np.int64)
    buf[0, :P] = prompt
    out = []
    with no_grad_guard():
        for i in range(int(max_new_tokens)):
            c = P + i
            logits = model(Tensor(buf, stop_gradient=True))
            nxt = int(sampler(np.asarray(logits.numpy())[0, c - 1], i))
            out.append(nxt)
            buf[0, c] = nxt
            if eos_id is not None and nxt == int(eos_id):
                break
    return out
