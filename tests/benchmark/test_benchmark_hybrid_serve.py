"""The cell lfm2_serve_saturated's own pieces (family lfm2_8b_a1b, runner
serve_decode_hybrid, lib/conv_mixer_ops.py and the two per-layer readers)
through the unedited harness at a tiny size on the CPU
(data/table_tiny_lfm2.json), each reader on a run written by hand and on a
program that records none of it (the parent, on which the driver tries new
readers), the configuration's arithmetic, and the controls that must fail
(control_lfm2.py). Entries of BENCHMARK.json are found by name: this file
holds no place and no count, so that the next cell reddens nothing here."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_lfm2.json')
NEW = ['conv_mixer_time_share', 'conv_mixer_roofline']
JOINED = ['serve_device_idle_share', 'serve_peak_hbm_gb',
          'kv_pool_fill_share', 'serve_mxu_time_share',
          'serve_compiles_in_window', 'decode_step_ms_p50',
          'prefill_time_share', 'slot_occupancy_mean', 'queue_wait_p50_ms',
          'serve_ttft_p50_ms', 'serve_itl_p50_ms', 'serve_itl_p90_ms',
          'moe_experts_time_share', 'moe_experts_roofline',
          'expert_load_max_over_mean']
# their readers would apply; their lists are pinned to one cell by tests
# this PR may not edit (ROADMAP's `benchmark` list)
PINNED = ['state_cache_bytes_per_slot', 'kv_decode_read_time_share',
          'kv_decode_read_roofline', 'prefill_attention_time_share',
          'prefill_attention_roofline', 'kv_cache_bytes_per_token',
          'engine_forward_share', 'scheduler_self_share',
          'worker_on_cpu_share', 'http_handler_cpu_share']
CELL = 'lfm2_serve_saturated'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
REDUCED = {'num_hidden_layers', 'num_dense_layers', 'layer_types'}


def _config():
    with open(os.path.join(BENCH, 'configs', 'lfm2_8b_a1b.json')) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(BENCH, 'traffic',
                           'closed_c128_ctx4k_v65k.json')) as f:
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


def test_the_configuration_is_the_catalog_entry_but_for_its_cut():
    """Every key of the published config.json at the top level of the file,
    under its own name; only the three cut keys differ, and say so; no
    width, no expert and no row of the vocabulary among them."""
    config = _config()
    if not os.path.exists(CATALOG):
        pytest.skip('no model-configs catalog on this machine')
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e['source_url'] == config['source'])
    assert entry['name'] == 'LFM2-8B-A1B'
    published = entry['config']
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config['reduced']) == REDUCED
    for key in ('num_hidden_layers', 'num_dense_layers'):
        assert config['published'][key] == published[key], key
    assert published['layer_types'].count('conv') == 18
    assert [i for i, t in enumerate(published['layer_types'])
            if t == 'full_attention'] == [2, 6, 10, 14, 18, 21]
    # the kept layers: published 0 and 2-13, three whole periods
    assert config['layer_types'] == [published['layer_types'][i]
                                     for i in [0] + list(range(2, 14))]
    assert config['layer_types'] == ['conv'] + [
        'full_attention', 'conv', 'conv', 'conv'] * 3
    assert (config['num_hidden_layers'], config['num_dense_layers']) \
        == (13, 1)
    # nothing else is cut: every width, every expert, the whole vocabulary
    assert (config['num_experts'], config['num_experts_per_tok'],
            config['moe_intermediate_size'], config['hidden_size'],
            config['intermediate_size']) == (32, 4, 1792, 2048, 7168)
    assert (config['num_attention_heads'], config['num_key_value_heads'],
            config['vocab_size'], config['conv_L_cache']) \
        == (32, 8, 65536, 3)
    assert config['hidden_size'] // config['num_attention_heads'] == 64
    assert (config['runner'], config['family']) == (
        'serve_decode_hybrid', 'lfm2_8b_a1b')
    for key in ('source', 'published', 'reduced', 'reduced_detail',
                'assumed', 'departures', 'deployment', 'dtype_policy'):
        assert config[key], key
    for key in ('tie_word_embeddings', 'head_dim', 'qk_norm', 'conv',
                'norms', 'router', 'initializer_range', 'block_size'):
        assert key in config['assumed'], key
    carried = config['assumed']['what config.json does not carry']
    assert 'transformers' in carried and 'NOT in the image' in carried
    assert '2 pipeline stages' in config['deployment']
    assert 'NOT run' in config['deployment']
    assert len(config['departures']) >= 5
    check = config['check']
    assert 0 < check['logit_tolerance'] < 0.1 and 0 < check['tie_margin']
    assert 0 < check['state_tolerance'] < 0.5
    for key in ('logit_tolerance_reason', 'state_tolerance_reason'):
        assert 'my chip runs, PR 38' in check[key], key


def test_the_configurations_arithmetic_is_the_files():
    """4.606 B parameters, 9.21 GB of bf16 weights, 3.52 GB of K/V pool
    over the 3 attention layers, 21 MB of conv state, 12.75 GB resident:
    from the widths in the file and the traffic's engine."""
    c, engine = _config(), _traffic()['engine']
    h, v = c['hidden_size'], c['vocab_size']
    heads, groups = c['num_attention_heads'], c['num_key_value_heads']
    d = h // heads
    attention = 2 * h * heads * d + 2 * h * groups * d + 2 * d
    assert attention == 10485888                              # 10.486 M
    conv = 3 * h * h + h * h + c['conv_L_cache'] * h
    assert conv == 16783360                                   # 16.783 M
    dense = 3 * h * c['intermediate_size']
    assert dense == 44040192
    expert = 3 * h * c['moe_intermediate_size']
    assert expert == 11010048
    experts = c['num_experts'] * expert + h * c['num_experts'] \
        + c['num_experts']
    assert experts == 352387104                               # 352.387 M
    norms = 2 * h
    dense_conv, sparse_conv, sparse_attention = (
        conv + dense + norms, conv + experts + norms,
        attention + experts + norms)
    assert (dense_conv, sparse_conv, sparse_attention) \
        == (60827648, 369174560, 362877088)
    kinds = c['layer_types']
    assert (kinds.count('conv'), kinds.count('full_attention')) == (10, 3)
    parameters = dense_conv + 9 * sparse_conv + 3 * sparse_attention \
        + v * h + h                     # the head is the embedding's array
    assert round(parameters / 1e9, 3) == 4.606
    weights = 2 * parameters
    assert round(weights / 1e9, 2) == 9.21
    assert round((weights + 2 * v * h) / 1e9, 2) == 9.48      # were it untied
    # the whole model: 2 dense and 22 sparse layers, 18 conv and 6 attention
    whole = 2 * (conv + dense + norms) + 16 * sparse_conv \
        + 6 * sparse_attention + v * h + h
    assert round(whole / 1e9, 2) == 8.34 and round(2 * whole / 1e9, 2) == 16.68
    token = 2 * groups * d * 2                       # K and V, bf16, a layer
    assert token == 2048 and 3 * token == 6144
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert per_slot == 280
    assert engine['max_blocks'] == engine['slots'] * per_slot + 8 == 35848
    pool = 3 * engine['max_blocks'] * engine['block_size'] * token
    assert round(pool / 1e9, 2) == 3.52
    states = (engine['slots'] + 1) * 10 * 2 * h * 4
    assert round(states / 1e6) == 21
    # 9.21 + 3.52 + 0.02: 12.75 GB as the addends are written, 12.758 whole
    assert 12.75e9 <= weights + pool + states < 12.76e9
    # a step's expert bytes: 12 layers of 32 experts, every one touched
    assert round(12 * 32 * expert * 2 / 1e9, 2) == 8.46
    assert engine['slots'] * c['num_experts_per_tok'] / c['num_experts'] == 16
    detail = c['reduced_detail']
    assert '4.606 B' in detail['num_hidden_layers']
    assert '9.21 GB' in detail['num_hidden_layers']
    assert '3.52 GB' in detail['kv_pool'] and '12.75 GB' in detail['kv_pool']
    assert '8.340 B' in detail['the cut'] and '8.46 GB' in detail[
        'a decode step']


def test_the_cell_is_sized_as_the_issue_says():
    traffic = _traffic()
    engine, load_ = traffic['engine'], traffic['load']
    assert traffic['runner'] == _config()['runner']
    assert (engine['slots'], load_['clients'], load_['loop']) \
        == (128, 128, 'closed')
    assert engine['prompt_buckets'] == [128, 256, 512, 1024, 2048, 4096]
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] \
        == engine['max_prompt_len']
    assert load_['prompt_len'] == {'median': 1024, 'sigma': 0.8, 'min': 128,
                                   'max': 4096}
    assert load_['output_len'] == {'median': 128, 'sigma': 0.6, 'min': 32,
                                   'max': 384}
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    assert load_['vocab'] == _config()['vocab_size'] == 65536
    assert (engine['kv_dtype'], engine['block_size'],
            engine['queue_depth']) == ('bf16', 16, 256)
    assert not (engine['prefix_cache'] or engine['spec_decode']
                or engine['disagg'])
    assert (traffic['check_prompts'], traffic['check_steps']) == (6, 16)
    # one prompt leaves its rung nearly half padding, one is a token short
    # of the top rung
    assert traffic['check_edge_prompts'] == [1025, 4095]
    # kanana2's lengths to the digit: what differs is the model
    with open(os.path.join(BENCH, 'traffic', 'closed_c128_ctx4k.json')) as f:
        kanana = json.load(f)
    assert kanana['load']['prompt_len'] == load_['prompt_len']
    assert kanana['load']['output_len'] == load_['output_len']
    assert {k: v for k, v in kanana['engine'].items()} == engine


def test_the_entries_are_found_by_name_and_the_cell_joins_the_lists():
    tab = table()
    cell = next(w for w in tab['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'lfm2_8b_a1b', 'closed_c128_ctx4k_v65k', 1)
    config = next(c for c in tab['configs'] if c['name'] == 'lfm2_8b_a1b')
    assert config['file'] == 'benchmark/configs/lfm2_8b_a1b.json'
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
    assert per_layer['conv_mixer_time_share']['better'] == 'lower'
    assert per_layer['conv_mixer_roofline']['better'] == 'higher'
    for name in JOINED:
        assert CELL in per_layer[name]['workloads'], name
    for name in PINNED:
        assert CELL not in per_layer[name]['workloads'], name
    e2e = {m['name']: m for m in tab['end_to_end']}
    assert CELL in e2e['serve_tokens_per_s']['workloads']
    for entry in (cell, config):
        assert len(entry['why']) <= 200


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/lfm2_8b_a1b.py')
    config = _config()
    h, f = 2048, 1792
    assert flops.layer_counts(config) == (3, 10)
    # a step: 128 rows x 4 over one expert layer, every expert touched
    got = flops.experts(config, 512, 32)
    assert got == (512 * 6 * h * f, 32 * 3 * h * f * 2 + 512 * 2 * h * 2)
    assert 3 * h * f * 2 == 22020096            # 22.0 MB an expert touched
    assert got[1] / 819e9 > got[0] / 197e12     # bytes bind a step
    # a read: K and V rows of 8 heads of 64 in bf16 a position a layer; 32
    # heads of a score and a weighted sum over 64
    fl, by = flops.decode_read(config, 1000)
    assert by == 1000 * 2048 and fl == 1000 * 32 * 4 * 64
    assert by / 819e9 > fl / 197e12
    fl, by = flops.prefill_attention(config, [1024, 128])
    assert fl == 3 * (1024 * 1025 // 2 + 128 * 129 // 2) * 32 * 4 * 64
    assert by == 3 * (1024 + 128) * 2 * (32 + 8) * 64 * 2
    # the conv operator: 8 h^2 a row a layer and the taps' 7 a channel;
    # 33.6 MB of projections a layer, the taps, a row in and out, the state
    weights = 10 * (4 * h * h + 3 * h) * 2
    assert round(4 * h * h * 2 / 1e6, 1) == 33.6
    fl, by = flops.conv_mixer(config, 128 * 10, step=True)
    assert fl == 1280 * (8 * h * h + 7 * h)
    assert by == weights + 1280 * 2 * h * 2 + 1280 * 2 * (2 * h * 4)
    assert by / 819e9 > fl / 197e12             # the weights bind a step
    fl, by = flops.conv_mixer(config, 1024 * 10, step=False)
    assert fl == 10240 * (8 * h * h + 7 * h)
    assert by == weights + 10240 * 2 * h * 2 + 10 * (2 * h * 4)
    assert fl / 197e12 > by / 819e9             # FLOPs bind a prefill


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
            (at + 'conv/step/jit(call_with)/dot_general', 1.5, 1.7),
            (at + 'conv/step/jit(call_with)/scatter', 0.8, 1.1),      # cut
            (at + 'conv/prefill/jit(call_with)/dot_general', 3.0, 3.2),
            (at + 'conv/prefill/jit(call_with)/mul', 3.2, 3.3),
            (at + 'moe/experts/pallas_call', 4.0, 4.3),
            (at + 'kv/decode_read/while/dot', 4.3, 4.4)], [
            ('engine/step', 2.5, dict(conv_rows=1280, state_updates=1280)),
            ('engine/step', 3.0, dict(conv_rows=1270, state_updates=1270)),
            ('engine/step', 3.5, dict(state_updates=64)),     # another's
            ('engine/prefill', 4.0, dict(prompt_len=1024, rung=1024,
                                         bucket=1024, conv_rows=10240)),
            ('engine/prefill', 6.5, dict(prompt_len=900, rung=1024,
                                         conv_rows=9000)),    # outside
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in NEW}
        finally:
            obs.reset()
    found = run['conv_mixer_ops']
    assert found['calls'] == [(True, 1280), (True, 1270), (False, 10240)]
    assert found['scopes'] == pytest.approx({'conv/step': 0.3,
                                             'conv/prefill': 0.3})
    assert values['conv_mixer_time_share'] == pytest.approx(30.0)
    flops = load('flops/lfm2_8b_a1b.py')
    config = _config()
    # each call priced for what binds IT: the steps by bytes, the prefill
    # by FLOPs
    least = sum(flops.conv_mixer(config, rows, True)[1] / 819e9
                for rows in (1280, 1270)) \
        + flops.conv_mixer(config, 10240, False)[0] / 197e12
    assert values['conv_mixer_roofline'] == pytest.approx(
        100 * least / 0.6)
    assert 0 < values['conv_mixer_roofline'] < 100


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_readers_find_nothing_where_the_program_has_no_conv_layer(
        monkeypatch):
    """The parent's traced run, or another model's: a device trace, other
    scopes, no `conv_rows` in the spans' args. Neither reader reads, and
    neither raises."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/kv/decode_read/dot_general', 1.5, 2.5),
            ('jit(run)/retention/decode_update/while', 2.5, 3.0)], [
            ('engine/step', 2.5, dict(context_positions=10 ** 6,
                                      state_updates=64)),
            ('engine/prefill', 3.0, dict(prompt_len=100, bucket=128))])
        try:
            got = {n: _reader(n).read(run, Ctx()) for n in NEW}
        finally:
            obs.reset()
    assert set(got.values()) == {None}
    assert run['conv_mixer_ops']['calls'] == []


def _rehearse(capsys, seed, trace=0, seconds='1'):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_hybrid', '--seed',
                       str(seed), '--seconds', seconds, '--trace',
                       str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    with open(os.path.join(BENCH, 'out', 'tiny_serve_hybrid',
                           'last_run.json')) as f:
        run = json.load(f)['run']
    return rc, json.loads(out.strip().splitlines()[-1]), run, out


@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_takes_family_runner_and_readers_through_the_harness(
        capsys, trace):
    rc, last, run, out = _rehearse(capsys, 2 ** 31 + 17, trace)
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
    for name in ('decode_conv_rows_total', 'decode_state_updates',
                 'decode_state_tokens_folded', 'decode_kv_blocks_read',
                 'decode_context_positions_read',
                 'decode_expert_assignments', 'state_cache_bytes_in_hbm',
                 'state_cache_rows_total', 'kv_cache_bytes_in_hbm'):
        assert value(name) > 0, name
    # three conv layers: every live row counted once a layer
    assert value('decode_conv_rows_total') == value(
        'decode_state_updates') + value('decode_state_tokens_folded')
    assert value('state_cache_rows_total') == 3
    assert value('state_cache_bytes_in_hbm') == 4 * 3 * 2 * 32 * 4
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) >= 1.0
    assert _reader('kv_pool_fill_share').read(run, Ctx()) > 0
    assert run['compiles']['window']['compiles'] == 0


@pytest.mark.parametrize('mode,by_state,rung_filled_sound', [
    ('weights_f8', False, False), ('state_zero', False, False),
    ('state_at_rung', True, True), ('taps_reversed', False, False),
    ('biased_weights', False, False), ('state_bf16', True, False)])
def test_each_control_reads_correct_false(capsys, mode, by_state,
                                          rung_filled_sound):
    """The controls of the configuration's `check` (control_lfm2.py) at the
    tiny size, in float32: each reads `correct` false with every answer
    exact. A state kept at the rung's end cannot show on the prompt that
    fills its rung (32) and shows on the others, by the logits of steps 1
    and 2 and by the state's own limit; a state lost at the prefill's end
    leaves the prefill's own row sound."""
    import control_lfm2
    harness = load('run.py', 'bench_run')
    restore = control_lfm2.plant(mode, harness)
    try:
        harness.main(['--workload', 'tiny_serve_hybrid', '--seed', '91',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_hybrid',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['every_answer_exact'] and checks['no_compile_in_window']
    errors = {e[0]: e[1:] for e in
              checks['logit_err_prompt_len_prefill_decode']}
    states = dict(checks['conv_state_err_prompt_len'])
    tolerance = checks['logit_tolerance']
    if mode != 'weights_f8':       # whose in_proj moves the state as well
        assert checks['state_within_tolerance'] is not by_state
    if mode == 'state_bf16':
        # rounding u to 8 bits moves a logit by far less than a weight's
        # loss of 5: the state's limit is what holds this statement
        assert max(states.values()) > 100 * checks['state_tolerance']
        return
    assert checks['logits_within_tolerance'] is False
    if mode == 'state_zero':
        # the prefill's row never reads a state; steps 1 and 2 do
        assert max(e[0] for e in errors.values()) < tolerance
        assert min(min(e[1:3]) for e in errors.values()) > 10 * tolerance
    elif mode == 'state_at_rung':
        assert max(errors[32]) < tolerance and states[32] < 1e-5
        assert min(errors[9][1:3]) > 10 * tolerance
        assert states[9] > 0.1 and states[31] > 0.1 and states[1] > 0.1
    else:
        assert min(max(e) for e in errors.values()) > 10 * tolerance
    assert sorted(control_lfm2.MODES) == [
        'biased_weights', 'state_at_rung', 'state_bf16', 'state_zero',
        'taps_reversed', 'weights_f8']


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference', 'lfm2_8b_a1b.py')) as f:
        source = f.read()
    assert 'paddle_tpu' not in source.split('"""', 2)[2]
    assert 'HIGHEST' in source and 'float32' in source
    assert 'import jax' in source and 'cache' not in source.split(
        '"""', 2)[2].replace('conv_L_cache', '')
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'lfm2_8b_a1b.py'))
