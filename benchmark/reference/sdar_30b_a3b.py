"""Plain reference for sdar_30b_a3b: the decoder of JetLM/SDAR-30B-A3B-Chat
(`model_type: sdar_moe`), whole-sequence forward under the BLOCK mask in
float32 jax.numpy at precision "highest", and block-diffusion generation
built on nothing but that forward. No cache, no kernels, no batching, no
grouped matmul, no framework code.

    x += Attn(norm1(x)); x += Experts(norm2(x)); logits = final_norm(x) · W_head

All norms RMSNorm (eps from the config), no biases. Attention: q = h·W_q as
32 heads of 128, k = h·W_k and v = h·W_v as 4 heads of 128; RMSNorm over each
head of q and of k (one weight of 128 each); RoPE (interleaved pairs (2i,
2i+1), theta from the config, no scaling) on q and k; query head j reads
key/value head j // 8; scores / sqrt(128); key j is VISIBLE to query i iff
j // B <= i // B, B the block length (`visible`: causal across blocks,
bidirectional inside one); softmax; Attn = concat_j(o_j)·W_o. Every layer
has `num_experts` gated experts behind a softmax router: p = softmax(h·W_r)
in float32 over all experts, the `num_experts_per_tok` largest chosen (of
equal scores the lower expert first), weighted by p normalised over the
chosen (`norm_topk_prob`); no shared expert, no dense layer. Every expert is
applied to every token, densely, one expert at a time, and weighted by zero
where it was not chosen. Row i of the logits is the distribution of position
i ITSELF (no shift).

Generation (`make_generate`): the prompt's whole blocks are context; the
first block is the prompt's last P mod B tokens, fixed, then `MASK` ids; a
forward of [context | block] gives the block's B rows; with the `MASK`
column at −∞ every masked position has a most likely token and a confidence
(its softmax probability); the ⌈masked₀ / denoising_steps⌉ most confident
masked positions take their tokens (ties to the lower position; a fixed
token never changes); when none is masked the block joins the context (a
system with a cache runs one more forward there, to keep the block's K/V:
here the next forward recomputes everything) and a new block of B masks
opens, until the answer holds the tokens asked for; the last block is cut.

Departures from the published model, all of the configuration and not of
this file: random weights from the seed; `num_hidden_layers` as the
configuration cuts it; the block length, the `MASK` id and the schedule are
the configuration's `assumed`.

Weights arrive under the program's parameter names, as the program stores
them (bf16 on the chip), and are cast up to float32 where they are used, an
expert or a slice at a time, so that the check fits beside the resident
model. Nothing else is taken from the program but the experts it reports it
chose at the checked positions, and those are judged, not trusted: as in
reference/kanana2_30b_a3b.py, `rows` follows a reported choice only where
the reference's OWN scores call it a near-tie (`tie_margin`, on the softmax
probabilities: every chosen expert within it of every expert left out), and
returns the gaps beside the rows.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HIGHEST)


def _norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight.astype(F32)


def _rope(x, pos, theta):
    """x (T, H, d): pairs (2i, 2i+1) turned by pos · theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = (pos.astype(F32)[:, None] * inv_freq)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                      even * jnp.sin(ang) + odd * jnp.cos(ang)],
                     -1).reshape(x.shape)


def visible(pos, block_length):
    """(T, T) bool, [i, j] true where key j is visible to query i: the block
    mask. (The control `causal_reference` of tests/benchmark/control_sdar.py
    swaps this for j <= i.)"""
    block_of = pos // block_length
    return block_of[None, :] <= block_of[:, None]


def _attention(p, name, m, h):
    t = h.shape[0]
    heads, groups, d = (m['num_attention_heads'], m['num_key_value_heads'],
                        m['head_dim'])
    pos = jnp.arange(t)
    eps, theta = m['rms_norm_eps'], m['rope_theta']
    q = _rope(_norm(_mm(h, p[name + '.q_proj.weight']).reshape(t, heads, d),
                    p[name + '.q_norm.weight'], eps), pos, theta)
    k = _rope(_norm(_mm(h, p[name + '.k_proj.weight']).reshape(t, groups, d),
                    p[name + '.k_norm.weight'], eps), pos, theta)
    v = _mm(h, p[name + '.v_proj.weight']).reshape(t, groups, d)
    seen = visible(pos, m['block_length'])
    rep = heads // groups

    def head(j):                     # one head at a time: (T, T) scores
        scores = jnp.matmul(q[:, j], k[:, j // rep].T,
                            precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.matmul(probs, v[:, j // rep], precision=HIGHEST)

    out = lax.map(head, jnp.arange(heads))               # (H, T, d)
    return _mm(out.transpose(1, 0, 2).reshape(t, heads * d),
               p[name + '.o_proj.weight'])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _experts(p, name, m, h, forced, tie_margin):
    """(routed output, gap (T,)). ``forced`` (T, k): the experts a system
    chose for each row, -1 where it reported none. A row's forced choice is
    followed where its gap (the largest probability left out less the
    smallest chosen) is at most ``tie_margin``; elsewhere, and where none
    was reported, the reference's own top-k stands. The gap returned is the
    forced choice's, or minus the own choice's margin."""
    k, n = m['num_experts_per_tok'], m['num_experts']
    s = jax.nn.softmax(_mm(h, p[name + '.router.weight']), -1)
    _, own = lax.top_k(s, k)
    given = forced[:, 0] >= 0
    asked = jnp.where(given[:, None], forced, own)
    inside = (asked[:, :, None] == jnp.arange(n)).any(1)            # (T, E)
    gap = jnp.where(inside, -jnp.inf, s).max(-1) \
        - jnp.where(inside, s, jnp.inf).min(-1)
    chosen = jnp.where((gap <= tie_margin)[:, None], asked, own)
    w = jnp.take_along_axis(s, chosen, -1)
    if m['norm_topk_prob']:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    dense = (w[:, :, None] * (chosen[:, :, None] == jnp.arange(n))
             ).sum(1)                                      # (T, E)

    def one(acc, e):
        y = _swiglu(h, p[name + '.experts_gate'][e],
                    p[name + '.experts_up'][e], p[name + '.experts_down'][e])
        return acc + dense[:, e][:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(n))
    return routed, gap


def hidden(p, m, ids, forced, tie_margin):
    """(final hidden states (T, h) before the last norm, gaps (layers, T))
    of the sequence ``ids`` (T,); ``forced`` (layers, T, k) as `_experts`
    takes it."""
    x = p['embed.weight'][ids].astype(F32)
    gaps = []
    for i in range(m['num_hidden_layers']):
        name = f'layers.{i}'
        x = x + _attention(p, name + '.attn', m, _norm(
            x, p[name + '.norm1.weight'], m['rms_norm_eps']))
        y, gap = _experts(p, name + '.ffn', m, _norm(
            x, p[name + '.norm2.weight'], m['rms_norm_eps']), forced[i],
            tie_margin)
        x = x + y
        gaps.append(gap)
    return x, jnp.stack(gaps)


def logits(p, m, x):
    """Rows x (n, h) through the last norm and the untied head, a slice of
    the vocabulary at a time."""
    x = _norm(x, p['final_norm.weight'], m['rms_norm_eps'])
    head = p['head.weight']
    pieces = 8 if head.shape[1] % 8 == 0 else 1
    width = head.shape[1] // pieces
    out = lax.map(lambda i: _mm(x, lax.dynamic_slice_in_dim(
        head, i * width, width, 1)), jnp.arange(pieces))  # (pieces, n, V/8)
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def model_of(config):
    """The sizes this file reads: the published keys sit at the top level of
    the configuration file, under their own names; the block length and the
    `MASK` id under `model`."""
    return {**config, **config['model']}


def make_rows(config, pad):
    """rows(params, ids, positions, forced=None, tie_margin=0.0) -> (logits
    rows at `positions` (n, V), their gaps (n, layers)), of the sequence
    `ids` (a whole number of blocks) padded to `pad` tokens so that every
    length shares one compiled program (padding comes in later blocks, which
    no earlier row sees, and experts act on each token alone). `forced`:
    {position: (layers, k) expert ids a system chose there}, followed where
    the reference's own scores call the choice a near-tie (`_experts`)."""
    model = model_of(config)
    shape = (model['num_hidden_layers'], pad, model['num_experts_per_tok'])

    def run(p, ids, positions, forced, tie_margin):
        x, gaps = hidden(p, model, ids, forced, tie_margin)
        return logits(p, model, x[positions]), gaps[:, positions].T

    fn = jax.jit(run)

    def rows(params, ids, positions, forced=None, tie_margin=0.0):
        if len(ids) % model['block_length'] or len(ids) > pad:
            raise ValueError(f'{len(ids)} tokens are no whole number of '
                             f"blocks of {model['block_length']} within "
                             f'{pad}')
        # padded on the host: a slice-update on the device would compile
        # once for every length
        buf = np.zeros((pad,), np.int32)
        buf[:len(ids)] = ids
        asked = np.full(shape, -1, np.int32)
        for position, chosen in (forced or {}).items():
            asked[:, position] = chosen
        return fn(params, buf, np.asarray(positions, np.int32), asked,
                  np.float32(tie_margin))
    return rows


def pick(rows, mask_token_id):
    """(ids (n,), confidences (n,)) of logits rows (n, V): the most likely
    token with the `MASK` column at −∞, and its softmax probability."""
    rows = np.array(rows, np.float64)
    rows[:, mask_token_id] = -np.inf
    ids = rows.argmax(-1)
    shifted = np.exp(rows - rows.max(-1, keepdims=True))
    return ids, shifted[np.arange(len(ids)), ids] / shifted.sum(-1)


def make_generate(config, pad):
    """generate(params, prompt, max_new_tokens, denoising_steps) -> the
    answer's tokens, by block diffusion on the whole-sequence forward (the
    module docstring); every sequence it forwards is padded to `pad`."""
    model = model_of(config)
    b, mask_id = model['block_length'], model['mask_token_id']
    rows = make_rows(config, pad)

    def generate(params, prompt, max_new_tokens, denoising_steps):
        whole = len(prompt) // b * b
        context, fixed = list(prompt[:whole]), list(prompt[whole:])
        answer = []
        while len(answer) < max_new_tokens:
            block = fixed + [mask_id] * (b - len(fixed))
            masked = [False] * len(fixed) + [True] * (b - len(fixed))
            quota = -(-sum(masked) // denoising_steps)
            at = list(range(len(context), len(context) + b))
            while any(masked):
                ids, conf = pick(rows(params, context + block, at)[0],
                                 mask_id)
                order = sorted((i for i in range(b) if masked[i]),
                               key=lambda i: (-conf[i], i))
                for i in order[:quota]:
                    block[i], masked[i] = int(ids[i]), False
            answer += block[len(fixed):]
            context, fixed = context + block, []
        return answer[:max_new_tokens]
    return generate
