"""The `nemotron_h` decoder (models/hybrid_ssm_moe_lm.py): Mamba-2 state
layers, latent relu² expert layers and grouped attention without rotary
positions in ONE model over the hybrid cache (serving/decode/kv_cache.py
"Hybrid models"). The system against the plain reference
(benchmark/reference/nemotron_h.py) on seeded weights, whole-sequence and
through the decode engine; the state's traps (a prefill's state is of the
prompt's TRUE end, not the rung's; a row taken again carries nothing over;
idle slots write the scratch row alone); the expert layer's shares adding
up to the whole layer; the reference's Mamba block against the image's
`transformers.models.mamba2` mixer with the same weights; the normal serve
path over HTTP; what the configuration and the cache refuse."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from paddle_tpu import dygraph
from paddle_tpu.models.hybrid_ssm_moe_lm import (HybridSSMMoEConfig,
                                                 HybridSSMMoELM)
from paddle_tpu.serving.decode.engine import DecodeEngine
from paddle_tpu.serving.decode.scheduler import DecodeScheduler
from paddle_tpu.serving.errors import UnsupportedCacheFeature

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
# of a row's largest logit: float32, a chunked scan and a fused program
# against a sequential recurrence (~1e-6 seen)
TOLERANCE = 2e-5
# of the state's largest value: the same float32 products, summed in
# another order by the chunked form (~1e-8 seen)
STATE_TOLERANCE = 1e-6
BLOCK, RUNGS = 4, [8, 16, 32]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load(os.path.join(REPO, 'benchmark', 'reference',
                               'nemotron_h.py'), 'reference_nemotron_h')
PROGRAM = _load(os.path.join(REPO, 'benchmark', 'programs',
                             'nemotron_h.py'), 'program_nemotron_h')


def _config(cfg):
    """The configuration-file form of a `HybridSSMMoEConfig`, as the
    reference reads it."""
    keys = ('vocab_size', 'hidden_size', 'num_hidden_layers',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'mamba_num_heads', 'mamba_head_dim', 'n_groups',
            'ssm_state_size', 'num_experts_per_tok', 'routed_scaling_factor',
            'norm_topk_prob', 'router_width', 'experts_held')
    config = {k: getattr(cfg, k) for k in keys}
    config.update(hybrid_override_pattern=cfg.pattern,
                  layer_norm_epsilon=cfg.rms_norm_eps,
                  n_routed_experts=cfg.num_experts_held,
                  model={'dtype': cfg.dtype})
    return config


def _model(seed=0, **overrides):
    from paddle_tpu.core.random import default_generator
    default_generator.seed(seed)
    model = HybridSSMMoELM(HybridSSMMoEConfig.tiny(**overrides))
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
    # 8 heads of 8 over 2 groups of 4, on the lanes: S 32 rows, the
    # window's 3 x 80 values 30 more
    assert (cfg.d_inner, cfg.conv_dim) == (64, 80)
    assert cfg.ssm_state_block == (62, 8)
    names = [n for n, _ in model.named_parameters()]
    assert 'head.weight' in names and 'norm_f.weight' in names
    mamba = {n.split('.', 3)[3] for n in names if n.startswith('layers.0.')
             and '.mixer.' in n}
    assert mamba == {'in_proj.weight', 'out_proj.weight', 'conv_w', 'conv_b',
                     'dt_bias', 'A_log', 'D', 'norm'}
    experts = {n.split('.', 3)[3] for n in names
               if n.startswith('layers.1.mixer.')}
    # latent, non-gated: no gate weights anywhere, no bias but the
    # router's selection bias and the filter's
    assert experts == {'router.weight', 'router_bias', 'latent_down.weight',
                       'latent_up.weight', 'experts_up', 'experts_down',
                       'shared.up.weight', 'shared.down.weight'}
    assert not [n for n in names if 'gate' in n]
    attention = {n.split('.', 3)[3] for n in names
                 if n.startswith('layers.3.mixer.')}
    assert attention == {'q_proj.weight', 'k_proj.weight', 'v_proj.weight',
                         'o_proj.weight'}
    values = _params(model)
    assert values['layers.1.mixer.experts_up'].shape == (8, 16, 16)
    assert values['layers.1.mixer.shared.up.weight'].shape == (32, 32)
    a = -np.exp(np.asarray(values['layers.0.mixer.A_log']))
    assert ((a <= -1) & (a >= -16)).all()
    # Δ at zero input is the drawn time step, within [min, max]
    dt = np.log1p(np.exp(np.asarray(values['layers.0.mixer.dt_bias'])))
    assert ((dt >= 0.001 - 1e-7) & (dt <= 0.1 + 1e-6)).all()
    layout = model.cache_layout()
    assert tuple(layer.kind for layer in layout.layers) == (
        'state', 'state', 'kv')
    assert (layout.ssm_layers, layout.conv_layers, layout.row_layers) \
        == (2, 0, 1)
    assert layout.layers[2].shape == (2, 8) and layout.reads == (
        ('groups', 1),)
    assert layout.state_row_bytes() == 2 * 8 * 62 * 4


def test_whole_sequence_logits_equal_the_reference():
    with dygraph.guard():
        model = _model(1)
        ids = np.random.RandomState(3).randint(1, 96, 29)
        got = model(dygraph.to_variable(ids[None])).numpy()[0]
    assert got.dtype == np.float32
    assert _worst(got, _want(model, ids.tolist(), list(range(29)))) \
        < TOLERANCE


@pytest.mark.parametrize('prompt_len', [1, 7, 8, 9, 15, 16, 17, 32])
def test_prefill_and_decode_through_the_cache_equal_the_reference(
        prompt_len):
    """A prompt of 1 token, of a rung less one, a rung, a rung and one (the
    next rung then nearly half padding), decoded 8 steps through the state
    rows and the K/V pool: every row against the reference's
    whole-sequence forward over the system's own tokens, and the first
    Mamba layer's state row right after the prefill against the
    reference's: of the prompt's TRUE end."""
    with dygraph.guard():
        model = _model(3)
        engine = _engine(model)
        prompt = np.random.RandomState(prompt_len).randint(
            1, 96, prompt_len).tolist()
        table = engine.reserve_table(prompt_len, 9)
        engine.prefill(prompt, table)
        held = np.asarray(PROGRAM.first_conv_state(engine, table))
        want_state = _want_state(model, prompt)
        assert held.shape == want_state.shape == (8 * 8 * 4 + 3 * 80,)
        assert _worst(held, want_state) < STATE_TOLERANCE
        engine.release_table(table)
        table, seq, rows = _decode(engine, prompt, 8)
        engine.release_table(table)
    assert len(rows) == 9
    want = _want(model, seq, list(range(prompt_len - 1, prompt_len + 8)))
    errors = [_worst(g, w) for g, w in zip(rows, want)]
    assert max(errors) < TOLERANCE, errors


def test_a_row_released_and_retaken_carries_nothing_over():
    """Slots turn over: a long request's row goes back and is taken by a
    one-token prompt, whose state and window must be its own (the window's
    two older rows zero), not the last request's."""
    with dygraph.guard():
        model = _model(4)
        engine = _engine(model)
        first, _, _ = _decode(engine, list(range(1, 20)), 6)
        row = first.state_row
        engine.release_table(first)
        table, seq, rows = _decode(engine, [5], 6)
        assert table.state_row == row                   # the same row
        engine.release_table(table)
        again = engine.reserve_table(1, 2)
        engine.prefill([5], again)
        held = np.asarray(PROGRAM.first_conv_state(engine, again))
        assert again.state_row == row
        window = held[8 * 8 * 4:].reshape(3, 80)
        assert not window[:2].any() and window[2].any()
        assert _worst(held, _want_state(model, [5])) < STATE_TOLERANCE
        engine.release_table(again)
        assert engine.pool.state_rows.used == 0
    want = _want(model, seq, list(range(len(rows))))
    assert max(_worst(g, w) for g, w in zip(rows, want)) < TOLERANCE


def test_idle_slots_write_the_scratch_row_alone():
    """Two live requests among three slots, stepped together: each equals
    the reference, the live rows are each their own, and the row nobody
    holds stays zero."""
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
        states = np.asarray(engine.pool.arrays()[0][0][0])     # (4, 62, 8)
        live = sorted(t.state_row for t in tables if t is not None)
        assert states.shape == (4, 62, 8)
        assert live == [1, 2] and not states[3].any()
        assert states[0].any() and not np.allclose(states[1], states[2])
    for slot, prompt in prompts.items():
        want = _want(model, seqs[slot][:-1],
                     list(range(len(prompt), len(prompt) + 6)))
        assert max(_worst(g, w) for g, w in zip(got[slot], want)) \
            < TOLERANCE


def test_the_expert_shares_add_up_to_the_whole_layer():
    """An expert layer cut into 4 shares of 2 experts (one chip's each under
    expert parallelism over 4): the router keeps its 8 outputs and its
    top-2; each share computes its own experts' part; the 4 parts, with the
    shared expert (which every chip computes alike) counted once, add up to
    the whole layer's output."""
    from paddle_tpu.core.random import default_generator
    from paddle_tpu.models.latent_moe_lm import RoutedExperts
    with dygraph.guard():
        default_generator.seed(8)
        whole = RoutedExperts(HybridSSMMoEConfig.tiny())
        values = {n: p.value for n, p in whole.named_parameters()}
        x = np.random.RandomState(2).randn(1, 7, 32).astype('float32')
        want = whole(dygraph.to_variable(x)).numpy()
        parts = []
        for first in range(0, 8, 2):
            share = RoutedExperts(HybridSSMMoEConfig.tiny(
                n_routed_experts=2, router_width=8,
                experts_held=(first, 2)))
            for name, p in share.named_parameters():
                p.value = values[name][first:first + 2] \
                    if name.startswith('experts_') else values[name]
            parts.append(share(dygraph.to_variable(x)).numpy())
        flat = dygraph.to_variable(x.reshape(7, 32))
        shared = whole.shared(flat).numpy().reshape(1, 7, 32)
    total = sum(parts) - 3 * shared
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()
    # each share alone is not the layer
    assert min(np.abs(p - want).max() for p in parts) > 1e-3


def test_served_over_http_on_the_normal_path():
    """An HTTP request to `ServingServer` over `build_replica_stack`
    streams tokens from the model through `DecodeScheduler`, `DecodeEngine`
    and `_Program`: greedy, each the argmax of the reference's row."""
    import http.client
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.tier.replica import build_replica_stack
    with dygraph.guard():
        model = _model(7)
        engine, scheduler, _ = build_replica_stack(
            model=model, slots=2, block_size=BLOCK, max_blocks=40,
            max_prompt_len=16, max_new_tokens_cap=8, prompt_buckets=[8, 16],
            prefix_cache=False, disagg=False, spec_decode=False)
        assert isinstance(scheduler, DecodeScheduler)
        assert isinstance(engine.model, HybridSSMMoELM)
        assert (engine.layout.state_layers, engine.layout.row_layers) \
            == (2, 1)
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


@pytest.mark.parametrize('feature,match', [
    (dict(prefix_cache=True), 'state layers'),
    (dict(spec_decode=True), 'state layers'),
    # the attention layer's grouped reads refuse int8 rows first
    (dict(kv_dtype='int8'), 'kv_dtype=int8')])
def test_a_state_layer_refuses_what_it_cannot_serve(feature, match):
    with dygraph.guard():
        model = _model()
        kw = dict(slots=2, block_size=BLOCK, max_blocks=40,
                  max_prompt_len=16, max_new_tokens_cap=8,
                  prompt_buckets=[8, 16], prefix_cache=False)
        kw.update(feature)
        with pytest.raises(UnsupportedCacheFeature, match=match):
            if 'spec_decode' in feature:
                from paddle_tpu.serving.tier.replica import \
                    build_replica_stack
                kw.pop('spec_decode')
                build_replica_stack(model=model, spec_decode=True,
                                    disagg=False, **kw)
            else:
                DecodeEngine(model, **kw)


# -- the configuration -------------------------------------------------------

@pytest.mark.parametrize('bad,match', [
    (dict(hybrid_override_pattern='ME-*E'), 'no equations'),
    (dict(hybrid_override_pattern='ME'), 'got 2 letters'),
    (dict(num_nextn_predict_layers=1), 'not held'),
    (dict(mlp_hidden_act='silu'), 'mlp_hidden_act'),
    (dict(use_conv_bias=False), 'use_conv_bias'),
    (dict(tie_word_embeddings=True), 'tie_word_embeddings'),
    (dict(n_shared_experts=2), 'n_shared_experts'),
    (dict(layer_types=['mamba']), 'unknown key'),
    (dict(expand=1), 'expand'),
    (dict(num_key_value_heads=3), 'divide'),
    (dict(router_width=16), 'experts_held'),
    (dict(n_routed_experts=4, router_width=8, experts_held=(6, 4)),
     'no range')])
def test_the_configuration_refuses_what_it_has_no_equations_for(bad, match):
    with pytest.raises(ValueError, match=match):
        HybridSSMMoEConfig.tiny(**bad)


def test_the_published_keys_build_the_cut_configuration():
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'nemotron3_super_120b_a12b.json')) as f:
        config = json.load(f)
    cfg = HybridSSMMoEConfig.from_published(config, **config['model'])
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (4096, 32, 2, 128)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.chunk_size) \
        == (128, 64, 8, 128, 4, 128)
    assert (cfg.d_inner, cfg.conv_dim) == (8192, 10240)
    assert cfg.ssm_state_block == (8432, 128)
    assert 8432 * 128 * 4 == 4317184
    assert (cfg.router_width, cfg.num_experts_held, cfg.experts_held,
            cfg.num_experts_per_tok) == (512, 128, (0, 128), 22)
    assert (cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.shared_expert_intermediate_size) == (2688, 1024, 5376)
    assert cfg.routed_scaling_factor == 5.0 and cfg.norm_topk_prob
    assert cfg.pattern == 'MEMEMEMEM*E' and cfg.num_hidden_layers == 11
    assert (cfg.vocab_size, cfg.rms_norm_eps) == (32768, 1e-5)
    assert cfg.dtype == 'bfloat16' and cfg.expert_activation == 'relu2'


# -- the reference against the public implementation --------------------------

@pytest.mark.parametrize('groups', [1, 2])
def test_the_references_mamba_block_equals_the_public_mamba2_mixer(groups):
    """The reference's `mamba_block` (the recurrence a row at a time) against
    the image's `transformers.models.mamba2` `Mamba2Mixer.torch_forward`
    (the chunked form) with the same weights: in_proj's order [z | xBC |
    dt], the filter, Δ, A, D, B and C of a head's group, the gate before
    the norm. The public Mamba-2 norms all lanes at once; `nemotron_h`
    norms groups of H·P / n_groups: with 2 groups the public norm is
    replaced by that grouped form."""
    torch = pytest.importorskip('torch')
    mamba2 = pytest.importorskip('transformers.models.mamba2.modeling_mamba2')
    from transformers.models.mamba2.configuration_mamba2 import Mamba2Config
    torch.manual_seed(groups)
    config = Mamba2Config(
        num_heads=8, head_dim=8, hidden_size=32, state_size=4, expand=2,
        conv_kernel=4, n_groups=groups, use_bias=False, use_conv_bias=True,
        chunk_size=4, layer_norm_epsilon=1e-5)
    mixer = mamba2.Mamba2Mixer(config, 0).eval()
    with torch.no_grad():
        for p in mixer.parameters():
            p.copy_(torch.randn_like(p) * 0.3)
        mixer.A_log.copy_(torch.log(torch.rand(8) * 15 + 1))
        mixer.dt_bias.copy_(torch.randn(8) * 0.5 - 2)
    if groups > 1:
        norm = mixer.norm

        class Grouped(torch.nn.Module):
            def forward(self, y, gate):
                g = y * torch.nn.functional.silu(gate)
                parts = g.view(*g.shape[:-1], groups, -1)
                parts = parts * torch.rsqrt(
                    parts.pow(2).mean(-1, keepdim=True) + 1e-5)
                return norm.weight * parts.view(*g.shape)
        mixer.norm = Grouped()
    tensor = lambda t: np.asarray(t.detach().numpy(), np.float32)
    p = {'m.in_proj.weight': tensor(mixer.in_proj.weight).T,
         'm.out_proj.weight': tensor(mixer.out_proj.weight).T,
         'm.conv_w': tensor(mixer.conv1d.weight)[:, 0].T,
         'm.conv_b': tensor(mixer.conv1d.bias),
         'm.dt_bias': tensor(mixer.dt_bias), 'm.A_log': tensor(mixer.A_log),
         'm.D': tensor(mixer.D),
         'm.norm': tensor(norm.weight if groups > 1 else mixer.norm.weight)}
    m = {'mamba_num_heads': 8, 'mamba_head_dim': 8, 'n_groups': groups,
         'ssm_state_size': 4, 'layer_norm_epsilon': 1e-5}
    a = np.random.RandomState(groups).randn(11, 32).astype('float32')
    with torch.no_grad():
        want = tensor(mixer.torch_forward(torch.from_numpy(a[None])))[0]
    with jax.default_matmul_precision('highest'):
        got = np.asarray(REFERENCE.mamba_block(p, 'm', m, a))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
