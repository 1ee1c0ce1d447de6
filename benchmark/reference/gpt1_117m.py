"""Plain reference for gpt1_117m: the decoder of "Improving Language
Understanding by Generative Pre-Training" (Radford et al. 2018) at the sizes
of openai-community/openai-gpt, whole-sequence forward in float32 jax.numpy
at precision "highest". No cache, no kernels, no batching, no framework code.

Token + learned position embeddings, L post-LN blocks (causal self-attention,
gelu feed-forward, residual + LayerNorm after each), logits through the tied
embedding. Departures, both of the program's model: a LayerNorm on the summed
embeddings (`emb_ln`; GPT-1 has none), and gelu in its exact erf form (GPT-1
used the tanh approximation).

Weights arrive under the program's parameter names; nothing else is taken
from the program. The block is the one of reference/bert_base.py: the
program builds both models from one TransformerLayer, and so does this.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_spec = importlib.util.spec_from_file_location(
    'benchmark_reference_bert_base',
    os.path.join(os.path.dirname(__file__), 'bert_base.py'))
_bert = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_bert)


def logits(params, model, ids):
    """(S, V) logits of one sequence of token ids (S,)."""
    s = ids.shape[0]
    x = params['word_emb.weight'][ids] + params['pos_emb.weight'][:s]
    x = _bert._ln(params, 'emb_ln', x)[None]
    for i in range(model['num_hidden_layers']):
        x = _bert.block(params, f'blocks.{i}', x,
                        model['num_attention_heads'], causal=True)
    return jnp.matmul(x[0], params['word_emb.weight'].T,
                      precision=lax.Precision.HIGHEST)


def make_rows(config):
    """rows(params, ids, positions): the logits rows at `positions` of the
    sequence `ids`, padded to the model's context so that every length shares
    one compiled program (padding after a position cannot reach it through a
    causal mask)."""
    model = config['model']
    pad = model['max_position_embeddings']
    fn = jax.jit(lambda p, x: logits(p, model, x))

    def rows(params, ids, positions):
        # padded on the host: a slice-update on the device would compile
        # once for every prompt length
        buf = np.zeros((pad,), np.int32)
        buf[:len(ids)] = ids
        return fn(params, buf)[np.asarray(positions, np.int32)]
    return rows
