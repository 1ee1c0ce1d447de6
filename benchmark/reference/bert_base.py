"""Plain reference for bert_base: BERT (Devlin et al. 2018, arXiv:1810.04805)
MLM + NSP pre-training loss, gradients and one Adam update, in float32
jax.numpy at precision "highest". No kernels, no mixed precision, no
framework code.

Post-LN encoder as published: embeddings (word + position + segment) with
LayerNorm, L blocks of self-attention and a gelu feed-forward each followed
by residual + LayerNorm (epsilon 1e-5), tanh pooler on the first token, the
MLM transform (dense, gelu, LayerNorm) with the decoder tied to the word
embedding plus a bias, a 2-way NSP head. gelu is the exact erf form. Dropout
is 0 (the configuration's departure). Adam is Kingma & Ba's algorithm 1.

Weights arrive under the program's parameter names; nothing else is taken
from the program.

The loss is a mean over masked positions and every sequence holds the same
number of them, so gradients are accumulated over shards of the batch: the
float32 logits of 128 sequences (2 GB, and as much again for each copy the
backward pass keeps) would not fit the chip beside the program. The sum is
the same.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _dense(p, name, x):
    return jnp.matmul(x, p[name + '.weight'], precision=HIGHEST) \
        + p[name + '.bias']


def _ln(p, name, x):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + 1e-5) * p[name + '.weight'] \
        + p[name + '.bias']


def _gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))


def block(p, prefix, x, heads, causal=False):
    """One post-LN transformer block; shared with the decoder reference."""
    b, s, h = x.shape
    d = h // heads

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(_dense(p, f'{prefix}.attn.{n}', x)) for n in 'qkv')
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k, precision=HIGHEST) \
        / math.sqrt(d)
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e9)
    ctx = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = _ln(p, prefix + '.attn_ln', x + _dense(p, prefix + '.attn.out', ctx))
    f = _dense(p, prefix + '.ffn2', _gelu(_dense(p, prefix + '.ffn1', x)))
    return _ln(p, prefix + '.ffn_ln', x + f)


def loss_sums(p, model, ids, segment, mlm, nsp):
    """(sum of MLM losses over masked positions, sum of NSP losses)."""
    b, s = ids.shape
    x = p['bert.word_emb.weight'][ids] \
        + p['bert.pos_emb.weight'][jnp.arange(s)][None] \
        + p['bert.type_emb.weight'][segment]
    x = _ln(p, 'bert.emb_ln', x)
    for i in range(model['num_hidden_layers']):
        x = jax.checkpoint(block, static_argnums=(1, 3))(
            p, f'bert.encoder.{i}', x, model['num_attention_heads'])
    pooled = jnp.tanh(_dense(p, 'bert.pooler', x[:, 0]))
    t = _ln(p, 'heads.transform_ln',
            _gelu(_dense(p, 'heads.transform', x)))
    logits = jnp.matmul(t, p['bert.word_emb.weight'].T, precision=HIGHEST) \
        + p['heads.decoder_bias']
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, jnp.maximum(mlm, 0)[..., None], -1)
    mlm_sum = -jnp.sum(jnp.where(mlm >= 0, picked[..., 0], 0.0))
    nsp_logp = jax.nn.log_softmax(_dense(p, 'heads.nsp', pooled))
    nsp_sum = -jnp.sum(jnp.take_along_axis(nsp_logp, nsp.reshape(-1, 1), 1))
    return mlm_sum, nsp_sum


def losses(config, params, batch, shard=16):
    """{'loss0', 'loss1'}: see reference/resnet50.py. Gradients are
    accumulated over shards of `shard` sequences."""
    model, o = config['model'], config['optimizer']
    n = batch[0].shape[0]
    shard = min(shard, n)
    assert n % shard == 0, (n, shard)
    masked = float(jnp.sum(batch[2] >= 0))

    def scaled(p, *b):
        # a shard's share of the batch loss: MLM over all masked positions
        # of the batch, NSP over all its sequences
        m, s = loss_sums(p, model, *b)
        return m / masked + s / n

    grad = jax.jit(jax.value_and_grad(scaled))
    value = jax.jit(scaled)
    shards = [tuple(a[i:i + shard] for a in batch)
              for i in range(0, n, shard)]

    l0, g = 0.0, None
    for b in shards:
        li, gi = grad(params, *b)
        l0 += float(li)
        g = gi if g is None else jax.tree_util.tree_map(jnp.add, g, gi)

    @jax.jit
    def adam(p, g):
        # first step from zero moments: the bias-corrected moments are g and
        # g^2, so the update is lr g / (|g| + epsilon)
        return jax.tree_util.tree_map(
            lambda w, d: w - o['learning_rate'] * d
            / (jnp.abs(d) + o['epsilon']), p, g)

    new = adam(params, g)
    return {'loss0': l0, 'loss1': sum(float(value(new, *b)) for b in shards)}
