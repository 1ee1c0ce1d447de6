"""The `lfm2_moe` decoder (models/hybrid_conv_moe_lm.py) and the HYBRID cache
(serving/decode/kv_cache.py "Hybrid models": state layers beside row layers
over one manager): the system against the plain reference
(benchmark/reference/lfm2_8b_a1b.py) on seeded weights, whole-sequence and
through the decode engine; the state's two traps (a prefill's state is of
the prompt's TRUE end, not the rung's; a row taken again carries nothing
over, and idle slots write the scratch row alone); the reference's conv
block, attention block and decoder layer against the image's
`transformers.models.lfm2` with the same weights, prefill and its cached
decode both; the normal serve path over HTTP; what the configuration
refuses."""
import importlib.util
import os

import jax
import numpy as np
import pytest

from paddle_tpu import dygraph
from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                  HybridConvMoELM)
from paddle_tpu.serving.decode.engine import DecodeEngine
from paddle_tpu.serving.decode.scheduler import DecodeScheduler

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
# of a row's largest logit: float32, a fused program against another order
# of the same sums (1e-6 seen). A bf16 router moves a score by up to 2e-3 and
# a bf16 state a value of u by up to 4e-3: each reads far above it
# (test_lower_precision_fails_the_tolerances)
TOLERANCE = 2e-5
# of the state's largest value: the same float32 products in both
STATE_TOLERANCE = 1e-6
BLOCK, RUNGS = 4, [8, 16, 32]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load(os.path.join(REPO, 'benchmark', 'reference',
                               'lfm2_8b_a1b.py'), 'reference_lfm2')
PROGRAM = _load(os.path.join(REPO, 'benchmark', 'programs',
                             'lfm2_8b_a1b.py'), 'program_lfm2')


def _config(cfg):
    """The configuration-file form of a `HybridConvMoEConfig`, as the
    reference reads it."""
    keys = ('vocab_size', 'hidden_size', 'intermediate_size',
            'moe_intermediate_size', 'num_hidden_layers', 'num_dense_layers',
            'num_attention_heads', 'num_key_value_heads', 'num_experts',
            'num_experts_per_tok', 'conv_L_cache', 'norm_topk_prob',
            'routed_scaling_factor', 'rope_theta')
    config = {k: getattr(cfg, k) for k in keys}
    config['norm_eps'] = cfg.rms_norm_eps
    config['layer_types'] = list(cfg.layer_types)
    config['model'] = {}
    return config


def _model(seed=0, **overrides):
    from paddle_tpu.core.random import default_generator
    default_generator.seed(seed)
    model = HybridConvMoELM(HybridConvMoEConfig.tiny(**overrides))
    model.eval()
    return model


def _params(model):
    return {n: p.value for n, p in model.named_parameters()}


def _want(model, ids, positions):
    rows = REFERENCE.make_rows(_config(model.cfg), REFERENCE.pad_of(len(ids)))
    with jax.default_matmul_precision('highest'):
        return np.asarray(rows(_params(model), ids, positions)[0])


def _want_state(model, prompt):
    state = REFERENCE.make_first_conv_state(_config(model.cfg), len(prompt))
    with jax.default_matmul_precision('highest'):
        return np.asarray(state(_params(model), prompt))


def _worst(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def _engine(model, slots=3, **kw):
    kw.setdefault('max_blocks', slots * 14 + 8)
    return DecodeEngine(model, slots=slots, block_size=BLOCK,
                        max_prompt_len=32, max_new_tokens_cap=24,
                        prompt_buckets=RUNGS, prefix_cache=False, **kw)


def _decode(engine, prompt, steps, slot=0):
    """(table, the prompt and the fed tokens, the prefill's row and every
    step's) of ``prompt`` prefilled and stepped in ``slot``, the others
    idle; the table is still held."""
    got = []

    def grab(row):
        got.append(np.array(row))
        return int(row.argmax())

    table = engine.reserve_table(len(prompt), steps + 1)
    fed = [engine.prefill(prompt, table, sampler=grab)]
    tokens, tables = [None] * engine.slots, [None] * engine.slots
    tables[slot] = table
    for _ in range(steps):
        tokens[slot] = fed[-1]
        ids, rows = engine.decode_step(tokens, tables, return_rows=True)
        got.append(np.array(rows[slot]))
        fed.append(int(ids[slot]))
    return table, list(prompt) + fed[:-1], got


def test_the_model_is_what_the_file_says():
    with dygraph.guard():
        model = _model()
    cfg = model.cfg
    assert cfg.head_dim == 8 and cfg.conv_state_block == (1, 2, 32)
    names = [n for n, _ in model.named_parameters()]
    # the head reads the embedding's array: no parameter of its own
    assert not [n for n in names if 'head' in n]
    assert 'embed.weight' in names and 'embedding_norm.weight' in names
    # a conv layer: two projections and the taps, no bias; no shared expert
    assert {n.split('.', 2)[2] for n in names if n.startswith('layers.0.')} \
        >= {'operator.in_proj.weight', 'operator.out_proj.weight',
            'operator.taps'}
    assert not [n for n in names if 'bias' in n and 'router_bias' not in n]
    assert not [n for n in names if 'shared' in n]
    layout = model.cache_layout()
    assert tuple(layer.kind for layer in layout.layers) == (
        'state', 'kv', 'state', 'state')
    assert layout.kind == 'kv' and layout.reads == (('groups', 1),)
    assert layout.layers[1].shape == (2, 8) and layout.layers[1].span == 0
    assert {layer.shape for layer in layout.layers
            if layer.kind == 'state'} == {(1, 2, 32)}


def test_whole_sequence_logits_equal_the_reference():
    with dygraph.guard():
        model = _model(1)
        ids = np.random.RandomState(3).randint(1, 96, 29)
        got = model(dygraph.to_variable(ids[None])).numpy()[0]
    assert got.dtype == np.float32
    assert _worst(got, _want(model, ids.tolist(), list(range(29)))) \
        < TOLERANCE


@pytest.mark.parametrize('taps', [2, 4])
def test_the_operator_is_written_for_l_taps(taps):
    with dygraph.guard():
        model = _model(2, conv_L_cache=taps)
        ids = np.random.RandomState(taps).randint(1, 96, 13)
        got = model(dygraph.to_variable(ids[None])).numpy()[0]
        assert _worst(got, _want(model, ids.tolist(), list(range(13)))) \
            < TOLERANCE
        engine = _engine(model)
        table, seq, rows = _decode(engine, ids[:9].tolist(), 5)
        assert engine.pool.arrays()[0][0][0].shape == (4, 1, taps - 1, 32)
    want = _want(model, seq, list(range(8, 8 + len(rows))))
    assert max(_worst(g, w) for g, w in zip(rows, want)) < TOLERANCE


@pytest.mark.parametrize('prompt_len', [1, 7, 8, 9, 15, 16, 17, 32])
def test_prefill_and_decode_through_both_kinds_equal_the_reference(
        prompt_len):
    """A prompt of 1 token, of a rung less one, a rung, a rung and one (the
    next rung then nearly half padding), decoded 8 steps through the state
    rows and the K/V pool: every row against the reference's
    whole-sequence forward over the system's own tokens, and the first conv
    layer's state row right after the prefill against the reference's: of
    the prompt's TRUE end."""
    with dygraph.guard():
        model = _model(3)
        engine = _engine(model)
        prompt = np.random.RandomState(prompt_len).randint(
            1, 96, prompt_len).tolist()
        got = []
        table = engine.reserve_table(prompt_len, 9)
        token = engine.prefill(prompt, table,
                               sampler=lambda row: got.append(np.array(row))
                               or int(row.argmax()))
        held = np.asarray(PROGRAM.first_conv_state(engine, table))
        want_state = _want_state(model, prompt)
        assert held.shape == want_state.shape == (2, 32)
        assert _worst(held, want_state) < STATE_TOLERANCE
        if prompt_len == 1:
            assert not held[0].any() and held[1].any()
        engine.release_table(table)
        table, seq, rows = _decode(engine, prompt, 8)
        engine.release_table(table)
    assert len(rows) == 9
    want = _want(model, seq, list(range(prompt_len - 1, prompt_len + 8)))
    errors = [_worst(g, w) for g, w in zip(rows, want)]
    assert max(errors) < TOLERANCE, errors


def test_the_rungs_end_is_another_state_and_shows(monkeypatch):
    """The fault the check exists for: a prefill that keeps the state of
    the RUNG's end. Planted in the op (`last` ignored), a 9-token prompt on
    the 16 rung reads a state and first decode steps far from the
    reference; a prompt that fills its rung cannot tell."""
    from paddle_tpu.dygraph.tape import kernel_cache
    from paddle_tpu.ops.registry import get_op
    opdef = get_op('short_conv_prefill')
    monkeypatch.setattr(opdef, 'fn',
                        lambda x, w, last=None, _fn=opdef.fn: _fn(x, w))
    kernel_cache.clear()
    try:
        with dygraph.guard():
            model = _model(3)
            engine = _engine(model)
            errors = {}
            for plen in (9, 16):
                prompt = np.random.RandomState(plen).randint(
                    1, 96, plen).tolist()
                table, seq, rows = _decode(engine, prompt, 4)
                engine.release_table(table)
                want = _want(model, seq, list(range(plen - 1, plen + 4)))
                errors[plen] = [_worst(g, w) for g, w in zip(rows, want)]
    finally:
        kernel_cache.clear()
    # the prefill's own row is right (the filter is causal): the steps that
    # read what it left are not
    assert errors[9][0] < TOLERANCE
    assert min(errors[9][1:3]) > 100 * TOLERANCE, errors
    assert max(errors[16]) < TOLERANCE


def test_a_row_released_and_retaken_carries_nothing_over():
    """Slots turn over: a long request's row goes back and is taken by a
    one-token prompt, whose state's older value must read zero, not the
    last request's; the rows it decodes equal the reference."""
    with dygraph.guard():
        model = _model(4)
        engine = _engine(model)
        first, _, _ = _decode(engine, list(range(1, 20)), 6)
        row = first.state_row
        assert np.asarray(PROGRAM.first_conv_state(engine, first)).all()
        engine.release_table(first)
        table, seq, rows = _decode(engine, [5], 6)
        assert table.state_row == row                   # the same row
        engine.release_table(table)
        again = engine.reserve_table(1, 2)
        engine.prefill([5], again)
        held = np.asarray(PROGRAM.first_conv_state(engine, again))
        assert again.state_row == row and not held[0].any()
        engine.release_table(again)
        assert engine.pool.state_rows.used == 0
        assert engine.pool.allocator.used == 0
    want = _want(model, seq, list(range(len(rows))))
    assert max(_worst(g, w) for g, w in zip(rows, want)) < TOLERANCE


def test_idle_slots_write_the_scratch_row_alone():
    """Two live requests among three slots, stepped together: each equals
    the reference, the live rows are each their own, and the rows nobody
    holds (but row 0, the idle slot's) stay zero."""
    with dygraph.guard():
        model = _model(5)
        engine = _engine(model)
        rng = np.random.RandomState(6)
        prompts = {0: rng.randint(1, 96, 11).tolist(),
                   2: rng.randint(1, 96, 5).tolist()}
        tables, seqs, got = [None] * 3, {}, {0: [], 2: []}
        for slot, prompt in prompts.items():
            tables[slot] = engine.reserve_table(len(prompt), 7)
            seqs[slot] = prompt + [engine.prefill(prompt, tables[slot])]
        for _ in range(6):
            ids, rows = engine.decode_step(
                [seqs[s][-1] if s in seqs else None for s in range(3)],
                tables, return_rows=True)
            for slot in prompts:
                got[slot].append(np.array(rows[slot]))
                seqs[slot].append(int(ids[slot]))
        states = np.asarray(engine.pool.arrays()[0][0][0])   # (4, 1, 2, h)
        live = sorted(t.state_row for t in tables if t is not None)
        assert live == [1, 2] and not states[3].any()
        assert states[0].any() and states[1].any() and states[2].any()
        assert not np.allclose(states[1], states[2])
    for slot, prompt in prompts.items():
        want = _want(model, seqs[slot][:-1],
                     list(range(len(prompt), len(prompt) + 6)))
        assert max(_worst(g, w) for g, w in zip(got[slot], want)) \
            < TOLERANCE


@pytest.mark.parametrize('what', ['router', 'state'])
def test_lower_precision_fails_the_tolerances(monkeypatch, what):
    """bfloat16 where float32 is stated: the router's scores (its logits
    rounded to bf16 before the sigmoid), or the conv state (rounded after
    every write). The state's limit sees the second at once; the logits'
    limit sees both."""
    import jax.numpy as jnp
    from paddle_tpu.dygraph.tape import kernel_cache
    from paddle_tpu.ops.registry import get_op
    if what == 'router':
        opdef = get_op('moe_router')

        def lower(x, w_gate, *args, _fn=opdef.fn, **kw):
            return _fn(jnp.asarray(x, jnp.bfloat16),
                       jnp.asarray(w_gate, jnp.bfloat16), *args, **kw)
        monkeypatch.setattr(opdef, 'fn', lower)
    else:
        for name in ('short_conv_prefill', 'short_conv_step'):
            opdef = get_op(name)

            def lower(*args, _fn=opdef.fn, **kw):
                out, state = _fn(*args, **kw)
                return out, state.astype(jnp.bfloat16).astype(jnp.float32)
            monkeypatch.setattr(opdef, 'fn', lower)
    kernel_cache.clear()
    try:
        with dygraph.guard():
            model = _model(3)
            engine = _engine(model)
            prompt = np.random.RandomState(1).randint(1, 96, 9).tolist()
            table, seq, rows = _decode(engine, prompt, 8)
            held = np.asarray(PROGRAM.first_conv_state(engine, table))
    finally:
        kernel_cache.clear()
    want = _want(model, seq, list(range(8, 17)))
    assert max(_worst(g, w) for g, w in zip(rows, want)) > 10 * TOLERANCE
    if what == 'state':
        # the row as the LAST step left it: the last two fed values
        assert held.dtype == np.float32
        assert (held.astype(jnp.bfloat16).astype(np.float32) == held).all()


def test_served_over_http_on_the_normal_path():
    """An HTTP request to `ServingServer` over `build_replica_stack`
    streams tokens from the model through `DecodeScheduler`, `DecodeEngine`
    and `_Program`: greedy, each the argmax of the reference's row."""
    import http.client
    import json
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.tier.replica import build_replica_stack
    with dygraph.guard():
        model = _model(7)
        engine, scheduler, _ = build_replica_stack(
            model=model, slots=2, block_size=BLOCK, max_blocks=40,
            max_prompt_len=16, max_new_tokens_cap=8, prompt_buckets=[8, 16],
            prefix_cache=False, disagg=False, spec_decode=False)
        assert isinstance(scheduler, DecodeScheduler)
        assert isinstance(engine.model, HybridConvMoELM)
        assert (engine.layout.state_layers, engine.layout.row_layers) \
            == (3, 1)
        server = ServingServer(None, host='127.0.0.1', port=0,
                               generator=scheduler)
        server.start()
        try:
            prompt = [9, 4, 77, 31, 2, 60, 18, 5, 44]
            conn = http.client.HTTPConnection('127.0.0.1', server.port,
                                              timeout=120)
            conn.request('POST', '/generate', json.dumps(
                {'prompt': prompt, 'max_new_tokens': 6, 'stream': True}),
                {'Content-Type': 'application/json'})
            resp = conn.getresponse()
            assert resp.status == 200
            body = resp.read().decode()
            conn.close()
        finally:
            server.shutdown(drain=False)
        tokens = [json.loads(line)['token'] for line in body.splitlines()
                  if line.strip() and 'token' in json.loads(line)]
    assert len(tokens) == 6
    seq = list(prompt)
    for token in tokens:
        want = _want(model, seq, [len(seq) - 1])[0]
        assert int(want.argmax()) == token
        seq.append(token)
    assert engine.pool.state_rows.used == 0
    assert engine.pool.allocator.used == 0


# -- the configuration ---------------------------------------------------------

@pytest.mark.parametrize('bad,match', [
    (dict(conv_bias=True), 'conv_bias'),
    (dict(rope_scaling={'type': 'yarn'}), 'rope_scaling'),
    (dict(use_expert_bias=False), 'use_expert_bias'),
    (dict(tie_word_embeddings=False), 'tie_word_embeddings'),
    (dict(num_shared_experts=1), 'unknown key'),
    (dict(layer_types=['conv', 'sliding_attention', 'conv', 'conv']),
     'sliding_attention'),
    (dict(layer_types=['conv']), 'got 1 entries'),
    (dict(conv_L_cache=1), 'conv_L_cache'),
    (dict(num_key_value_heads=3), 'divide')])
def test_the_configuration_refuses_what_it_has_no_equations_for(bad, match):
    with pytest.raises(ValueError, match=match):
        HybridConvMoEConfig.tiny(**bad)


def test_the_published_keys_build_the_cut_configuration():
    import json
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'lfm2_8b_a1b.json')) as f:
        config = json.load(f)
    cfg = HybridConvMoEConfig.from_published(config, **config['model'])
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size) \
        == (32, 4, 1792, 7168)
    assert (cfg.num_hidden_layers, cfg.num_dense_layers) == (13, 1)
    assert cfg.layer_types == ('conv',) + (
        'full_attention', 'conv', 'conv', 'conv') * 3
    assert (cfg.vocab_size, cfg.conv_L_cache, cfg.rms_norm_eps,
            cfg.rope_theta) == (65536, 3, 1e-5, 1e6)
    assert cfg.router_norm_epsilon == 1e-6 and cfg.n_shared_experts == 0
    assert cfg.dtype == 'bfloat16' and cfg.conv_state_block == (1, 2, 2048)


# -- the reference against the public implementation --------------------------

@pytest.fixture(scope='module')
def hf():
    """The image's `transformers.models.lfm2` at a small size, seeded:
    (torch, its config, an `Lfm2Model` of conv, attention, conv)."""
    torch = pytest.importorskip('torch')
    pytest.importorskip('transformers.models.lfm2')
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    from transformers.models.lfm2.modeling_lfm2 import Lfm2Model
    torch.manual_seed(0)
    config = Lfm2Config(
        vocab_size=96, hidden_size=32, intermediate_size=48,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, norm_eps=1e-5, rope_theta=1e6,
        conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
        layer_types=['conv', 'full_attention', 'conv'],
        attn_implementation='eager')
    model = Lfm2Model(config).eval()
    with torch.no_grad():
        for p in model.parameters():
            p.copy_(torch.randn_like(p) * 0.3)
    return torch, config, model


def _rope_lanes(d):
    """Lane r of a head as this repo turns it (pairs (2i, 2i+1)) is lane
    r // 2 (r even) or r // 2 + d/2 (r odd) of the public implementation's
    (pairs (i, i + d/2))."""
    return np.asarray([r // 2 + (d // 2) * (r % 2) for r in range(d)])


def _reference_params(model, config):
    """The `Lfm2Model`'s weights under the program's parameter names, as
    the reference reads them: a Linear's weight transposed, the taps (L,
    h), the q and k projections' columns and their norms' lanes permuted
    from the public RoPE convention to this repo's."""
    d = config.hidden_size // config.num_attention_heads
    lanes = _rope_lanes(d)
    tensor = lambda t: np.asarray(t.detach().numpy(), np.float32)

    def heads(weight, n):                       # (n·d, h) -> (h, n·d)
        w = tensor(weight).T.reshape(config.hidden_size, n, d)
        return w[:, :, lanes].reshape(config.hidden_size, n * d)

    p = {'embed.weight': tensor(model.embed_tokens.weight),
         'embedding_norm.weight': tensor(model.embedding_norm.weight)}
    for i, layer in enumerate(model.layers):
        name = f'layers.{i}'
        p[name + '.operator_norm.weight'] = tensor(layer.operator_norm.weight)
        p[name + '.ffn_norm.weight'] = tensor(layer.ffn_norm.weight)
        p[name + '.ffn.gate.weight'] = tensor(layer.feed_forward.w1.weight).T
        p[name + '.ffn.up.weight'] = tensor(layer.feed_forward.w3.weight).T
        p[name + '.ffn.down.weight'] = tensor(layer.feed_forward.w2.weight).T
        op = name + '.operator'
        if layer.is_attention_layer:
            a = layer.self_attn
            p[op + '.q_proj.weight'] = heads(a.q_proj.weight,
                                             config.num_attention_heads)
            p[op + '.k_proj.weight'] = heads(a.k_proj.weight,
                                             config.num_key_value_heads)
            p[op + '.v_proj.weight'] = tensor(a.v_proj.weight).T
            p[op + '.o_proj.weight'] = tensor(a.out_proj.weight).T
            p[op + '.q_norm.weight'] = tensor(a.q_layernorm.weight)[lanes]
            p[op + '.k_norm.weight'] = tensor(a.k_layernorm.weight)[lanes]
        else:
            c = layer.conv
            p[op + '.in_proj.weight'] = tensor(c.in_proj.weight).T
            p[op + '.out_proj.weight'] = tensor(c.out_proj.weight).T
            p[op + '.taps'] = tensor(c.conv.weight)[:, 0, :].T     # (L, h)
    return p


def _reference_model(config):
    return {'num_attention_heads': config.num_attention_heads,
            'num_key_value_heads': config.num_key_value_heads,
            'head_dim': config.hidden_size // config.num_attention_heads,
            'norm_eps': config.norm_eps, 'rope_theta': config.rope_theta,
            'layer_types': list(config.layer_types),
            'num_hidden_layers': config.num_hidden_layers,
            'num_dense_layers': config.num_hidden_layers,
            'conv_L_cache': config.conv_L_cache}


def _hf_mask(torch, t):
    mask = torch.full((t, t), float('-inf')).triu(1)
    return mask[None, None]


def test_the_references_blocks_equal_the_public_implementations(hf):
    """`conv_block`, `attention_block` and `decoder_layer` of the reference
    against `Lfm2ShortConv`, `Lfm2Attention` and `Lfm2DecoderLayer` with
    the same weights, over one sequence."""
    torch, config, model = hf
    p, m = _reference_params(model, config), _reference_model(config)
    t = 11
    x = torch.randn(1, t, config.hidden_size)
    xs = np.asarray(x[0].numpy())
    positions = torch.arange(t)[None]
    rotary = model.pos_emb(x, positions)
    with torch.no_grad(), jax.default_matmul_precision('highest'):
        conv = model.layers[0].conv.slow_forward(x)[0].numpy()
        assert _worst(REFERENCE.conv_block(p, 'layers.0.operator', xs),
                      conv) < 1e-5
        attn = model.layers[1].self_attn(x, rotary, _hf_mask(torch, t))[0]
        assert _worst(REFERENCE.attention_block(p, 'layers.1.operator', m,
                                                xs), attn[0].numpy()) < 1e-5
        for i in range(3):
            layer = model.layers[i](x, position_embeddings=rotary,
                                    attention_mask=_hf_mask(torch, t),
                                    position_ids=positions)[0].numpy()
            got, gap = REFERENCE.decoder_layer(p, m, i, xs)
            assert gap is None and _worst(got, layer) < 1e-5, i


def test_the_references_whole_sequence_equals_the_public_cached_decode(hf):
    """`Lfm2Model` with its own hybrid cache: a prefill of 7 tokens, then 6
    tokens one at a time, each reading the conv state and the K/V the cache
    holds; the reference's whole-sequence forward over the same 13 tokens,
    with no cache and no state, gives the same hidden states after
    `embedding_norm`, and the same conv state after the prefill."""
    torch, config, model = hf
    p, m = _reference_params(model, config), _reference_model(config)
    ids = torch.randint(1, 96, (1, 13))
    with torch.no_grad():
        out = model(input_ids=ids[:, :7], use_cache=True)
        cache = out.past_key_values
        # the public cache keeps L columns, the oldest never read again
        carried = cache.conv_cache[0][0].numpy().T[1:].copy()
        rows = [out.last_hidden_state[0].numpy()]
        for t in range(7, 13):
            out = model(input_ids=ids[:, t:t + 1], past_key_values=cache,
                        use_cache=True,
                        cache_position=torch.tensor([t]))
            rows.append(out.last_hidden_state[0].numpy())
    want = np.concatenate(rows)
    with jax.default_matmul_precision('highest'):
        x = p['embed.weight'][ids[0].numpy()]
        for i in range(3):
            x, _ = REFERENCE.decoder_layer(p, m, i, x)
        got = REFERENCE._norm(x, p['embedding_norm.weight'], m['norm_eps'])
        state = REFERENCE.first_conv_state(p, m, ids[0].numpy(), 7)
    assert _worst(got, want) < 1e-5
    assert _worst(state, carried) < 1e-6
