"""The cell nemotron3_serve_saturated's own pieces (family nemotron_h, the
hybrid runner serve_decode_hybrid, lib/ssm_mixer_ops.py and the four
per-layer readers) through the unedited harness at a tiny size on the CPU
(data/table_tiny_nemotron3.json), each reader on a run written by hand and
on a program that records none of it (one without the Mamba layers, as a
run of the commit before them would be), the configuration against the
published config.json (data/published_nemotron3.json, the catalog row as
the public file gives it) and its arithmetic, and the controls that must
fail (control_nemotron3.py). Entries of
BENCHMARK.json are found by name: this file holds no place and no count, so
that the next cell reddens nothing here."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_nemotron3.json')
NEW = ['ssm_step_time_share', 'ssm_prefill_time_share', 'ssm_step_roofline',
       'ssm_prefill_roofline']
JOINED = ['serve_device_idle_share', 'serve_peak_hbm_gb',
          'kv_pool_fill_share', 'serve_mxu_time_share',
          'serve_compiles_in_window', 'decode_step_ms_p50',
          'prefill_time_share', 'slot_occupancy_mean', 'queue_wait_p50_ms',
          'serve_ttft_p50_ms', 'serve_itl_p50_ms', 'serve_itl_p90_ms',
          'moe_experts_time_share', 'moe_experts_roofline',
          'expert_load_max_over_mean']
# their readers would apply; their lists are pinned to their cells by tests
# of earlier cells
PINNED = ['state_cache_bytes_per_slot', 'kv_decode_read_time_share',
          'kv_decode_read_roofline', 'prefill_attention_time_share',
          'prefill_attention_roofline', 'kv_cache_bytes_per_token',
          'expert_held_assignment_share', 'engine_forward_share',
          'engine_device_wait_share', 'engine_logits_copy_share',
          'engine_sample_share', 'logits_copy_bytes_per_token',
          'scheduler_self_share', 'serve_idle_in_forward_share',
          'serve_idle_unattributed_share', 'worker_on_cpu_share',
          'worker_lock_wait_share', 'engine_forward_offcpu_share',
          'http_handler_cpu_share', 'serve_idle_in_worker_python_share']
CELL = 'nemotron3_serve_saturated'
CONFIG = 'nemotron3_super_120b_a12b'
PUBLISHED = os.path.join(DATA, 'published_nemotron3.json')
REDUCED = {'num_hidden_layers', 'hybrid_override_pattern', 'n_routed_experts',
           'vocab_size', 'num_nextn_predict_layers'}


def _config():
    with open(os.path.join(BENCH, 'configs', CONFIG + '.json')) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(BENCH, 'traffic',
                           'closed_c128_in4k_out6k_v32k.json')) as f:
        return json.load(f)


class Ctx:
    """What a reader asks of the harness's Context."""
    stats = load('lib/stats.py')
    xplane = load('lib/xplane.py')
    config = _config()
    traffic = _traffic()
    trace_file = 'a.xplane.pb'

    def module(self, kind, name):
        return load(f'{kind}/{name}.py')


def _reader(name):
    return load(f'layer_metrics/{name}.py')


def test_the_configuration_is_the_published_one_but_for_its_cut():
    """Every key of the published config.json at the top level of the file,
    under its own name; only the five cut keys differ, and say so; no width
    among them."""
    config = _config()
    with open(PUBLISHED) as f:
        entry = json.load(f)
    assert entry['source_url'] == config['source']
    assert entry['name'] == 'NVIDIA-Nemotron-3-Super-120B-A12B-BF16'
    published = entry['config']
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config['reduced']) == REDUCED
    for key in REDUCED:
        assert config['published'][key] == published[key], key
    pattern = published['hybrid_override_pattern']
    assert (len(pattern), pattern.count('M'), pattern.count('E'),
            pattern.count('*')) == (88, 40, 40, 8)
    # the kept layers: published 27-37, one whole period of 11
    assert config['hybrid_override_pattern'] == pattern[27:38] \
        == 'MEMEMEMEM*E'
    assert config['num_hidden_layers'] == 11
    assert (config['n_routed_experts'], config['router_width'],
            config['experts_held']) == (128, 512, [0, 128])
    assert (config['vocab_size'], config['num_nextn_predict_layers']) \
        == (32768, 0)
    assert published['vocab_size'] == 4 * config['vocab_size']
    # nothing else is cut: every width as published
    for key in ('hidden_size', 'mamba_num_heads', 'mamba_head_dim',
                'n_groups', 'ssm_state_size', 'conv_kernel', 'chunk_size',
                'moe_intermediate_size', 'moe_latent_size',
                'moe_shared_expert_intermediate_size', 'num_experts_per_tok',
                'num_attention_heads', 'num_key_value_heads', 'head_dim'):
        assert config[key] == published[key], key
    assert (config['runner'], config['family']) == (
        'serve_decode_hybrid', 'nemotron_h')
    for key in ('source', 'published', 'reduced', 'reduced_detail',
                'assumed', 'departures', 'deployment', 'dtype_policy'):
        assert config[key], key
    for key in ('no rotary embedding', 'latent placement', 'router input'):
        assert key in config['assumed'], key
    departures = ' '.join(config['departures'])
    assert 'multi-token prediction' in departures and 'random' in departures
    assert '4 chips' in config['deployment']
    assert 'NOT run' in config['deployment']
    check = config['check']
    assert 0 < check['logit_tolerance'] < 0.1 and 0 < check['tie_margin']
    assert 0 < check['state_tolerance'] < 0.01
    for key in ('logit_tolerance_reason', 'state_tolerance_reason',
                'tie_margin_reason'):
        assert 'on a TPU v5e' in check[key], key


def test_the_configurations_arithmetic_is_the_files():
    """4.648 B parameters, 9.30 GB of bf16 weights, 2.78 GB of state rows
    over the 5 Mamba layers, 1.34 GB of K/V over the one attention layer:
    13.42 GB resident; a step's 7.05 GB of experts, 5.53 GB of state read
    and written and 1.10 GB of Mamba weights: from the widths in the file
    and the traffic's engine."""
    c, engine = _config(), _traffic()['engine']
    h, v = c['hidden_size'], c['vocab_size']
    heads, p = c['mamba_num_heads'], c['mamba_head_dim']
    g, n, k = c['n_groups'], c['ssm_state_size'], c['conv_kernel']
    d_inner, conv = heads * p, heads * p + 2 * g * n
    assert (d_inner, conv) == (8192, 10240) == (c['expand'] * h, 10240)
    w_in, w_out = h * (d_inner + conv + heads), d_inner * h
    assert (round(w_in / 1e6, 2), round(w_out / 1e6, 2)) == (76.02, 33.55)
    mixer = w_in + w_out + (k + 1) * conv + d_inner + 3 * heads
    assert round(mixer / 1e6, 1) == 109.6
    assert round(2 * mixer / 1e6, 1) == 219.3          # MB read a step
    mamba = mixer + h                                   # and the block norm
    lat, f, fs = (c['moe_latent_size'], c['moe_intermediate_size'],
                  c['moe_shared_expert_intermediate_size'])
    expert = 2 * lat * f
    assert expert == 5505024 and round(2 * expert / 1e6, 2) == 11.01
    router = h * c['router_width'] + c['router_width']
    experts = c['n_routed_experts'] * expert + 2 * h * fs + 2 * h * lat \
        + router + h
    assert round(experts / 1e6, 1) == 759.2
    assert round(2 * experts / 1e9, 2) == 1.52
    q, kv = c['num_attention_heads'], c['num_key_value_heads']
    d = c['head_dim']
    attention = 2 * h * q * d + 2 * h * kv * d + h
    assert round(attention / 1e6, 1) == 35.7
    kinds = c['hybrid_override_pattern']
    assert (kinds.count('M'), kinds.count('E'), kinds.count('*')) \
        == (5, 5, 1)
    parameters = 5 * mamba + 5 * experts + attention + 2 * v * h + h
    assert round(parameters / 1e9, 3) == 4.648
    weights = 2 * parameters
    assert round(weights / 1e9, 2) == 9.30
    row = heads * (p * n + (k - 1) * conv // heads) * 4
    assert row == 4317184
    states = (engine['slots'] + 1) * 5 * row
    assert round(states / 1e9, 2) == 2.78
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert per_slot == 640
    assert engine['max_blocks'] == engine['slots'] * per_slot + 8 == 81928
    token = 2 * kv * d * 2                          # K and V, bf16
    assert token == 1024
    pool = engine['max_blocks'] * engine['block_size'] * token
    assert round(pool / 1e9, 2) == 1.34
    assert round((weights + states + pool) / 1e9, 2) == 13.42
    # a step: every held expert touched (128 x 22 x 128 / 512 = 704
    # assignments a layer, 5.5 an expert), each slot's state read and
    # written, the Mamba layers' weights
    assert engine['slots'] * c['num_experts_per_tok'] \
        * c['n_routed_experts'] / c['router_width'] / 128 == 5.5
    assert round(5 * 128 * 2 * expert / 1e9, 2) == 7.05
    assert round(engine['slots'] * 5 * 2 * row / 1e9, 2) == 5.53
    assert round(5 * 2 * mixer / 1e9, 2) == 1.10
    detail = c['reduced_detail']
    assert '4.648 B' in detail['num_hidden_layers']
    assert '9.30 GB' in detail['num_hidden_layers']
    assert '13.42 GB' in detail['memory']


def test_the_cell_is_sized_as_its_traffic_says():
    traffic = _traffic()
    engine, load_ = traffic['engine'], traffic['load']
    assert traffic['runner'] == _config()['runner']
    assert (engine['slots'], load_['clients'], load_['loop']) \
        == (128, 128, 'closed')
    assert engine['prompt_buckets'] == [128, 256, 512, 1024, 2048, 4096]
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] \
        == engine['max_prompt_len']
    assert load_['prompt_len'] == {'median': 1024, 'sigma': 0.8, 'min': 256,
                                   'max': 4096}
    assert load_['output_len'] == {'median': 1536, 'sigma': 0.7, 'min': 256,
                                   'max': 6144}
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    assert load_['vocab'] == _config()['vocab_size'] == 32768
    assert (engine['kv_dtype'], engine['block_size'],
            engine['queue_depth']) == ('bf16', 16, 256)
    assert not (engine['prefix_cache'] or engine['spec_decode']
                or engine['disagg'])
    assert (traffic['check_prompts'], traffic['check_steps']) == (6, 16)
    assert traffic['check_edge_prompts'] == [1025, 4095]


def test_the_entries_are_found_by_name_and_the_cell_joins_the_lists():
    tab = table()
    cell = next(w for w in tab['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        CONFIG, 'closed_c128_in4k_out6k_v32k', 1)
    config = next(c for c in tab['configs'] if c['name'] == CONFIG)
    assert config['file'] == f'benchmark/configs/{CONFIG}.json'
    assert set(config['reduced']) == REDUCED
    assert config['reduced'] == _config()['reduced']
    assert config['source'] == _config()['source']
    per_layer = {m['name']: m for m in tab['per_layer']}
    for name in NEW:
        m = per_layer[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'serve_tokens_per_s'
        assert m['layer'] == _reader(name).LAYER == 'ops_kernels'
        assert m['unit'] == _reader(name).UNIT == '%'
        assert m['source'] == 'device_trace'
        assert _reader(name).NAME == name
        assert m['better'] == ('lower' if 'time_share' in name else 'higher')
    for name in JOINED:
        assert CELL in per_layer[name]['workloads'], name
    for name in PINNED:
        assert CELL not in per_layer[name]['workloads'], name
    listed = {m['name'] for m in tab['per_layer']
              if CELL in m.get('workloads', ())}
    assert listed == set(NEW) | set(JOINED)
    e2e = {m['name']: m for m in tab['end_to_end']}
    assert CELL in e2e['serve_tokens_per_s']['workloads']
    for entry in (cell, config):
        assert len(entry['why']) <= 200


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/nemotron_h.py')
    config = _config()
    assert flops.layer_counts(config) == (1, 5, 5)
    assert flops.state_row_bytes(config) == 4317184
    # a step's experts: 128 slots x 22 over 512 experts, a quarter held
    got = flops.experts(config, 704, 128)
    assert got == (704 * 4 * 1024 * 2688,
                   128 * 2 * 1024 * 2688 * 2 + 704 * 2 * 1024 * 2)
    assert got[1] / 819e9 > got[0] / 197e12     # bytes bind a step
    # a step's mixers: 128 live slots x 5 layers
    fl, by = flops.ssm_step(config, 640)
    weights = 5 * 219271936
    assert by == weights + 640 * (2 * 4317184 + 2 * 4096 * 2)
    assert fl > 640 * 2 * 109.6e6
    assert by / 819e9 > 10 * fl / 197e12        # bytes bind, by far
    # a prefill of 1,024 tokens over 5 layers: FLOPs bind
    fl, by = flops.ssm_prefill(config, 5120)
    per_row = fl / 5120
    assert 2 * 109.6e6 < per_row < 2 * 109.6e6 * 1.05
    assert fl / 197e12 > by / 819e9


def _planes(ops):
    """A decoded trace: marks at 1 s and 5 s on the trace's clock (2 s and
    6 s on perf_counter), and chip 0's ops as (tf_op, start_s, end_s)."""
    xplane = Ctx.xplane
    host = {'name': '/host:CPU', 'lines': {'python3': [
        (xplane.mark_name('begin', int(2e9)), int(1e12), int(1e12), {}),
        (xplane.mark_name('end', int(6e9)), int(5e12), int(5e12), {})]}}
    device = {'name': '/device:TPU:0', 'lines': {'XLA Ops': [
        ('%fusion', int(a * 1e12), int(b * 1e12), {'tf_op': name})
        for name, a, b in ops]}}
    return [host, device]


def _traced(monkeypatch, obs, ops, calls):
    """A run with a device trace busy 2 s, the planes above, and engine
    spans (name, midpoint on perf_counter, args)."""
    monkeypatch.setattr(Ctx.xplane, '_decode',
                        lambda path, want_line=None: _planes(ops))
    obs.reset()
    for name, mid, args in calls:
        obs.tracer.complete(name, mid - 0.01, mid + 0.01, **args)
    return {'registry': {}, 'trace': {'chips': [{'busy_s': 2.0}]},
            'peaks': {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}}


def test_time_and_roofline_shares_over_the_slices_own_calls(monkeypatch):
    from paddle_tpu import observability as obs
    at = 'jit(run)/jit(main)/'
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            (at + 'ssm/step/jit(call_with)/dot_general', 1.5, 1.7),
            (at + 'ssm/step/jit(call_with)/while', 0.8, 1.1),        # cut
            (at + 'ssm/prefill/jit(call_with)/dot_general', 3.0, 3.2),
            (at + 'ssm/prefill/jit(call_with)/scan', 3.2, 3.3),
            (at + 'moe/experts/pallas_call', 4.0, 4.3),
            (at + 'conv/step/jit(call_with)/mul', 4.3, 4.4)], [
            ('engine/step', 2.5, dict(ssm_rows=640, state_updates=640)),
            ('engine/step', 3.0, dict(ssm_rows=635, state_updates=635)),
            ('engine/step', 3.5, dict(conv_rows=64)),          # another's
            ('engine/prefill', 4.0, dict(prompt_len=1024, rung=1024,
                                         bucket=1024, ssm_rows=5120)),
            ('engine/prefill', 6.5, dict(prompt_len=900, rung=1024,
                                         ssm_rows=4500)),      # outside
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in NEW}
        finally:
            obs.reset()
    found = run['ssm_mixer_ops']
    assert found['calls'] == [(True, 640), (True, 635), (False, 5120)]
    assert found['scopes'] == pytest.approx({'step': 0.3, 'prefill': 0.3})
    assert values['ssm_step_time_share'] == pytest.approx(15.0)
    assert values['ssm_prefill_time_share'] == pytest.approx(15.0)
    flops = load('flops/nemotron_h.py')
    config = _config()
    # each call priced for what binds IT: the steps by bytes, the prefill
    # by FLOPs; each scope's calls over its own device seconds
    steps = sum(flops.ssm_step(config, rows)[1] / 819e9
                for rows in (640, 635))
    prefill = flops.ssm_prefill(config, 5120)[0] / 197e12
    assert values['ssm_step_roofline'] == pytest.approx(100 * steps / 0.3)
    assert values['ssm_prefill_roofline'] == pytest.approx(
        100 * prefill / 0.3)


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_readers_find_nothing_where_the_program_has_no_mamba_layer(
        monkeypatch):
    """The parent's traced run, or another model's: a device trace, other
    scopes, no `ssm_rows` in the spans' args. No reader reads, and none
    raises."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/kv/decode_read/dot_general', 1.5, 2.5),
            ('jit(run)/conv/step/mul', 2.5, 3.0)], [
            ('engine/step', 2.5, dict(context_positions=10 ** 6,
                                      conv_rows=64)),
            ('engine/prefill', 3.0, dict(prompt_len=100, bucket=128))])
        try:
            got = {n: _reader(n).read(run, Ctx()) for n in NEW}
        finally:
            obs.reset()
    assert set(got.values()) == {None}
    assert run['ssm_mixer_ops']['calls'] == []


def _rehearse(capsys, seed, trace=0, seconds='1'):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_ssm', '--seed', str(seed),
                       '--seconds', seconds, '--trace', str(trace)],
                      rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    with open(os.path.join(BENCH, 'out', 'tiny_serve_ssm',
                           'last_run.json')) as f:
        run = json.load(f)['run']
    return rc, json.loads(out.strip().splitlines()[-1]), run, out


@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_takes_family_runner_and_readers_through_the_harness(
        capsys, trace):
    rc, last, run, out = _rehearse(capsys, 2 ** 31 + 19, trace)
    assert rc == 0 and last['correct'] is True, out[-3000:]
    assert last['attempted'] > 0 and last['failed'] == 0
    assert run['runner'] == 'serve_decode'
    errors = run['checks']['logit_err_prompt_len_prefill_decode']
    # the shortest prompt, the longest, the two edges, two draws; per prompt
    # its length, the prefill's row, steps 1, 2 and 6
    assert [e[0] for e in errors][:4] == [1, 32, 9, 31] and len(errors) == 6
    assert all(len(e) == 5 and 0 < max(e[1:]) < 1e-4 for e in errors)
    states = run['checks']['conv_state_err_prompt_len']
    assert [e[0] for e in states] == [e[0] for e in errors]
    assert all(e[1] < 1e-5 for e in states)
    assert run['checks']['state_within_tolerance'] is True
    assert run['checks']['every_answer_exact'] is True
    if not trace:
        assert set(last['metrics']) == {'serve_tokens_per_s', 'setup_s'}
        return
    # off a TPU there is no device plane: the trace readers are left out
    assert not set(NEW) & set(last['metrics'])
    assert set(JOINED) - {
        'serve_device_idle_share', 'serve_mxu_time_share',
        'serve_peak_hbm_gb', 'moe_experts_time_share',
        'moe_experts_roofline'} <= set(last['metrics'])
    registry = run['registry']
    value = lambda n: registry[n]['samples'][0]['value']
    for name in ('decode_ssm_rows_total', 'decode_state_updates',
                 'decode_state_tokens_folded', 'decode_kv_blocks_read',
                 'decode_context_positions_read',
                 'decode_expert_assignments', 'state_cache_bytes_in_hbm',
                 'state_cache_rows_total', 'kv_cache_bytes_in_hbm'):
        assert value(name) > 0, name
    # two Mamba layers, the model's only state layers: every live row
    # counted once a layer, by the SSM counter and the state's alike
    assert value('decode_ssm_rows_total') == value(
        'decode_state_updates') + value('decode_state_tokens_folded')
    assert 'decode_conv_rows_total' not in registry \
        or value('decode_conv_rows_total') == 0
    assert value('state_cache_rows_total') == 3
    assert value('state_cache_bytes_in_hbm') == 4 * 2 * 8 * 62 * 4
    # the held share: 4 of the router's 8 experts
    assert 0 < value('decode_expert_assignments_held') \
        < value('decode_expert_assignments_total')
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) >= 1.0
    assert _reader('kv_pool_fill_share').read(run, Ctx()) > 0
    assert run['compiles']['window']['compiles'] == 0


@pytest.mark.parametrize('mode,by_state', [
    ('weights_f8', True), ('state_bf16', True), ('padding_enters', True),
    ('norm_whole', False), ('relu', False), ('biased_weights', False)])
def test_each_control_reads_correct_false(capsys, mode, by_state):
    """The controls of the configuration's `check` (control_nemotron3.py) at
    the tiny size, in float32: each reads `correct` false with every answer
    exact. The system's faults move the first Mamba layer's state (a
    padding row that enters S cannot show on the prompt that fills its
    rung, 32, and shows on the others); the reference's move the logits
    alone."""
    import control_nemotron3
    harness = load('run.py', 'bench_run')
    restore = control_nemotron3.plant(mode, harness)
    try:
        harness.main(['--workload', 'tiny_serve_ssm', '--seed', '91',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_ssm',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['every_answer_exact'] and checks['no_compile_in_window']
    states = dict(checks['conv_state_err_prompt_len'])
    assert checks['state_within_tolerance'] is not by_state
    if mode == 'padding_enters':
        errors = {e[0]: e[1:] for e in
                  checks['logit_err_prompt_len_prefill_decode']}
        assert states[32] < 1e-5 and max(errors[32]) < 1e-4
        assert min(v for k, v in states.items() if k != 32) > 100 * \
            checks['state_tolerance']
    elif mode == 'state_bf16':
        # bf16's 8 bits against a float32 state: a hundred times the limit
        assert max(states.values()) > 100 * checks['state_tolerance']
    else:
        assert checks['logits_within_tolerance'] is False
    assert sorted(control_nemotron3.MODES) == [
        'biased_weights', 'norm_whole', 'padding_enters', 'relu',
        'state_bf16', 'weights_f8']


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference', 'nemotron_h.py')) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert 'paddle_tpu' not in code
    assert 'HIGHEST' in source and 'float32' in source
    assert 'import jax' in source and 'cache' not in code.replace(
        'make_first_conv_state', '')
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'nemotron_h.py'))
