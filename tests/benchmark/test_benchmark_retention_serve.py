"""The cell brumby_serve_saturated's own pieces (family brumby_14b, runner
serve_decode_recurrent, lib/retention_ops.py and the five per-layer readers)
through the unedited harness at a tiny size on the CPU
(data/table_tiny_brumby.json), and each reader on a run written by hand and
on a program that records none of it (the parent, on which the driver tries
new readers). Entries of BENCHMARK.json are found by name, never by place."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_brumby.json')
NEW = ['retention_decode_time_share', 'retention_prefill_time_share',
       'retention_decode_roofline', 'retention_prefill_roofline',
       'state_cache_bytes_per_slot']
JOINED = ['serve_device_idle_share', 'serve_peak_hbm_gb',
          'serve_mxu_time_share', 'serve_compiles_in_window',
          'decode_step_ms_p50', 'prefill_time_share', 'slot_occupancy_mean',
          'queue_wait_p50_ms', 'serve_ttft_p50_ms', 'serve_itl_p50_ms',
          'serve_itl_p90_ms']
# the engine's, the scheduler's and the idle gaps' own, read since PR 24 in
# the GPT-1 cell: the same host path runs here
HOST = ['engine_forward_share', 'engine_device_wait_share',
        'engine_logits_copy_share', 'engine_sample_share',
        'logits_copy_bytes_per_token', 'scheduler_self_share',
        'serve_idle_in_forward_share', 'serve_idle_unattributed_share']
CELL = 'brumby_serve_saturated'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
# the first state layer against the reference's (my chip runs, PR 30): the
# largest sound reading, and the largest with the state held in bfloat16
SOUND_STATE_MAX, FAILING_STATE_MIN = 0.0095, 0.038


def _config():
    with open(os.path.join(BENCH, 'configs', 'brumby_14b.json')) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(BENCH, 'traffic', 'closed_c16_ctx4k.json')) as f:
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


def test_the_configuration_is_the_catalog_entry_but_for_its_depth():
    """Every key of the published config.json at the top level of the file,
    under its own name; only num_hidden_layers differs, and says so."""
    config = _config()
    if not os.path.exists(CATALOG):
        pytest.skip('no model-configs catalog on this machine')
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e['source_url'] == config['source'])
    assert entry['name'] == 'Brumby-14B-Base'
    differs = {k for k, v in entry['config'].items() if config.get(k) != v}
    assert differs == set(config['reduced']) == {'num_hidden_layers'}
    assert config['published']['num_hidden_layers'] \
        == entry['config']['num_hidden_layers'] == 40
    assert config['num_hidden_layers'] in (7, 8)          # the floor is 4
    assert (config['hidden_size'], config['intermediate_size'],
            config['num_attention_heads'], config['num_key_value_heads'],
            config['head_dim'], config['vocab_size']) == (
                5120, 17408, 40, 8, 128, 151936)
    for key in ('assumed', 'departures', 'deployment', 'dtype_policy',
                'reduced_detail'):
        assert config[key], key
    assert config['model']['dtype'] == 'bfloat16'
    assert 5.0 <= config['model']['gate_shift'] <= 7.0
    check = config['check']
    assert 0 < check['logit_tolerance'] < 0.1
    assert 'bfloat16' in check['logit_tolerance_reason']
    # the state's own limit, between its sound and its failing reading
    assert SOUND_STATE_MAX < check['state_tolerance'] < FAILING_STATE_MIN
    assert 'bfloat16' in check['state_tolerance_reason']
    assert 'first_state' in check['state_tolerance_reason']
    # what no limit holds is said, not claimed
    assert 'no limit' in config['dtype_policy']['state_contractions']


def test_the_cell_is_sized_as_the_issue_says():
    traffic, config = _traffic(), _config()
    engine, load_ = traffic['engine'], traffic['load']
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert per_slot == 304
    assert engine['max_blocks'] == engine['slots'] * per_slot + 1 == 4865
    assert engine['slots'] == load_['clients'] == 16
    assert engine['prompt_buckets'] == [128, 256, 512, 1024, 2048, 4096]
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] \
        == engine['max_prompt_len']
    assert load_['prompt_len'] == {'median': 1024, 'sigma': 0.8, 'min': 128,
                                   'max': 4096}
    assert load_['output_len'] == {'median': 256, 'sigma': 0.6, 'min': 64,
                                   'max': 768}
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    assert load_['vocab'] == config['vocab_size'] and load_['loop'] == 'closed'
    assert not (engine['prefix_cache'] or engine['disagg']
                or engine['spec_decode'])
    assert 'kv_dtype' not in engine          # a state cache has no choice
    assert (traffic['check_prompts'], traffic['check_decode_steps'],
            traffic['trace_slice_s'], traffic['request_timeout_s']) == (
                4, 64, 4, 600)
    # resident: bf16 weights and slots + 1 state rows of every layer
    layers, h, f, v = (config['num_hidden_layers'], 5120, 17408, 151936)
    layer = h * 40 * 128 * 2 + h * 8 * 128 * 2 + h * 8 + 3 * h * f \
        + 2 * h + 2 * 128
    assert layer == 330352896                     # 330.35 M
    weights = 2 * (layers * layer + 2 * v * h + h)
    # as the arrays hold it: 8 blocks of (8328, 128) float32 a row a layer
    states = 17 * layers * 8 * 8328 * 128 * 4
    if layers == 8:
        assert round(weights / 1e9, 2) == 8.40
        assert round(states / 1e9, 2) == 4.64
        assert round((weights + states) / 1e9, 1) == 13.0


def test_the_entries_are_appended_and_the_cell_joins_the_lists_by_name():
    """Found by name: the cell, its configuration, the eleven serve metrics
    and the eight of the host path it joins, and the five it brings; whatever a later PR appends after them
    is no concern of this test."""
    tab = table()
    cell = next(w for w in tab['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'brumby_14b', 'closed_c16_ctx4k', 1)
    config = next(c for c in tab['configs'] if c['name'] == 'brumby_14b')
    assert config['file'] == 'benchmark/configs/brumby_14b.json'
    assert config['reduced'] == ['num_hidden_layers'] == _config()['reduced']
    assert config['source'] == _config()['source']
    per_layer = {m['name']: m for m in tab['per_layer']}
    for name in NEW:
        m = per_layer[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'serve_tokens_per_s'
        assert m['layer'] == _reader(name).LAYER
        assert m['unit'] == _reader(name).UNIT
    for name in JOINED + HOST:
        assert CELL in per_layer[name]['workloads']
    for name in HOST:
        assert per_layer[name]['workloads'] == ['gpt1_serve_saturated', CELL]
    # no HBM stands behind this cell's blocks: not the pool's fill share
    assert CELL not in per_layer['kv_pool_fill_share']['workloads']
    e2e = {m['name']: m for m in tab['end_to_end']}
    assert CELL in e2e['serve_tokens_per_s']['workloads']
    # appended: every entry the benchmark had before comes before them
    names = [m['name'] for m in tab['per_layer']]
    assert max(names.index(n) for n in JOINED + HOST) < min(
        names.index(n) for n in NEW)
    assert [w['name'] for w in tab['workloads']].index(CELL) > \
        [w['name'] for w in tab['workloads']].index('kanana2_serve_saturated')
    for entry in (cell, config):
        assert len(entry['why']) <= 200


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/brumby_14b.py')
    config = _config()
    assert 128 * 129 // 2 == 8256
    assert flops.state_values(config) == 8256 * 129
    assert flops.state_bytes(config) == 8 * 8256 * 129 * 4 == 34080768
    # a step of 16 live slots over 8 layers: one read of each state; bytes
    # bind (41.6 us a slot-layer against 0.43)
    f, b = flops.decode_update(config, 16 * 8)
    assert f == 128 * 2 * 40 * 8256 * 129 and b == 128 * 34080768
    assert b / 819e9 > 50 * f / 197e12
    # a prefill: the quadratic form below ~8,300 tokens, the recurrence
    # above; both build the state
    build = 8 * 2 * 8256 * 129
    assert flops.prefill_scan_flops(config, 1000) == \
        40 * (1000 * 1001 // 2) * (2 * 128 + 2 * 129) + 1000 * build
    assert flops.prefill_scan_flops(config, 20000) == \
        20000 * (40 * 2 * 8256 * 129 + build)
    assert flops.prefill_scan_flops(config, 8000) < \
        8000 * (40 * 2 * 8256 * 129 + build)
    f, b = flops.prefill_scan(config, [1000, 300])
    assert f == config['num_hidden_layers'] * (
        flops.prefill_scan_flops(config, 1000)
        + flops.prefill_scan_flops(config, 300))
    assert b == config['num_hidden_layers'] * 2 * 34080768
    assert flops.prefill_scan(config, []) == (0, 0)


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


def test_scoped_time_and_rooflines_over_the_slices_own_calls(monkeypatch):
    from paddle_tpu import observability as obs
    scope = 'jit(run)/jit(main)/retention/'
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            (scope + 'decode_update/jit(call_with)/while/body/add', 1.5, 1.9),
            (scope + 'decode_update/jit(call_with)/dot_general', 0.8, 1.1),
            (scope + 'prefill_scan/jit(call_with)/while/body/dot_general',
             2.0, 2.2),
            (scope + 'prefill_scan/jit(call_with)/exp', 4.9, 5.4),  # cut at 5
            ('jit(run)/jit(main)/dot_general', 3.5, 3.6)], [
            ('engine/step', 2.5, dict(state_updates=128, kv_blocks=0)),
            ('engine/step', 3.0, dict(state_updates=120)),
            ('engine/prefill', 3.5, dict(state_tokens_folded=8000,
                                         prompt_len=1000, bucket=1024)),
            ('engine/prefill', 4.0, dict(state_tokens_folded=2400,
                                         prompt_len=300, bucket=512)),
            ('engine/prefill', 4.5, dict(prompt_len=700, bucket=1024)),
            ('engine/step', 6.5, dict(state_updates=128)),      # outside
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in NEW[:4]}
        finally:
            obs.reset()
    found = run['retention_ops']
    assert found['calls'] == 5
    assert found['state_updates'] == 248
    assert found['prompt_lens'] == [1000, 300]   # not another model's 700
    assert found['scopes']['retention/decode_update'] == pytest.approx(0.5)
    assert found['scopes']['retention/prefill_scan'] == pytest.approx(0.3)
    assert values['retention_decode_time_share'] == pytest.approx(25.0)
    assert values['retention_prefill_time_share'] == pytest.approx(15.0)
    config = _config()
    flops = load('flops/brumby_14b.py')
    assert values['retention_decode_roofline'] == pytest.approx(
        100 * (248 * 34080768 / 819e9) / 0.5)
    assert values['retention_prefill_roofline'] == pytest.approx(
        100 * (flops.prefill_scan(config, [1000, 300])[0] / 197e12) / 0.3)
    assert all(0 < v < 100 for v in values.values())


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}},
    {'registry': {'state_cache_bytes_in_hbm':
                  {'type': 'gauge', 'samples': []}}, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    run.setdefault('counts', {})
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_trace_readers_find_nothing_where_the_program_names_no_scope(
        monkeypatch):
    """The parent's traced run, or another model's: a device trace, none of
    these scopes, no state work in the spans' args."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/mla/decode_read/dot_general', 1.5, 2.5)], [
            ('engine/step', 2.5, dict(context_positions=10 ** 6)),
            ('engine/prefill', 3.0, dict(prompt_len=900, bucket=1024))])
        try:
            for name in NEW[:4]:
                assert _reader(name).read(run, Ctx()) is None, name
        finally:
            obs.reset()
    assert run['retention_ops']['prompt_lens'] == []


def test_registry_reader_on_a_run_written_by_hand():
    run = {'counts': {}, 'registry': {
        'state_cache_bytes_in_hbm': {'type': 'gauge', 'samples': [
            {'labels': {}, 'value': 8 * 17 * 8 * 8328 * 128 * 4}]},
        'state_cache_rows_total': {'type': 'gauge', 'samples': [
            {'labels': {}, 'value': 16}]}}}
    per_slot = _reader('state_cache_bytes_per_slot').read(run, Ctx())
    assert per_slot == 8 * 8 * 8328 * 128 * 4 == 272891904
    # within 0.1% of the mathematics' 8 layers x 34.08 MB
    assert per_slot / (8 * 34080768) < 1.001


@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_takes_family_runner_and_readers_through_the_harness(
        capsys, trace):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_recurrent', '--seed',
                       str(2 ** 31 + 13), '--seconds', '1', '--trace',
                       str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last['correct'] is True, out[-3000:]
    assert last['attempted'] > 0 and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_recurrent',
                           'last_run.json')) as f:
        run = json.load(f)['run']
    assert run['runner'] == 'serve_decode'
    errors = run['checks']['logit_err_prompt_len_prefill_decode']
    # two rounds: a prompt in each of the 4 slots, then 3 on the freed rows;
    # per prompt its length, the prefill's row, step 1, step 10
    assert len(errors) == 4 + 3 and all(len(e) == 4 for e in errors)
    assert [e[0] for e in errors[:2]] == [4, 16]
    assert all(max(e[1:]) < 1e-4 for e in errors)
    # the state's own limit: every prompt's row of the first state layer,
    # as its prefill and as its last step left it, against the reference's
    states = run['checks']['state_err_prompt_len_folded_walked']
    assert [e[0] for e in states] == [e[0] for e in errors]
    assert all(len(e) == 3 and 0 < max(e[1:]) < 1e-5 for e in states)
    assert run['checks']['state_within_tolerance'] is True
    if not trace:
        assert set(last['metrics']) == {'serve_tokens_per_s', 'setup_s'}
        return
    # off a TPU there is no device plane: the trace readers are left out,
    # the registry's is there (null off the chip: not a count)
    assert 'state_cache_bytes_per_slot' in last['metrics']
    assert not set(NEW[:4]) & set(last['metrics'])
    assert set(JOINED + HOST) - {
        'serve_device_idle_share', 'serve_mxu_time_share',
        'serve_peak_hbm_gb', 'serve_idle_in_forward_share',
        'serve_idle_unattributed_share'} <= set(last['metrics'])
    # the step's rows copy is (slots, V) float32 whatever the cache
    assert _reader('logits_copy_bytes_per_token').read(run, Ctx()) >= 4 * 96
    assert 0 < _reader('engine_device_wait_share').read(run, Ctx()) < 100
    assert _reader('state_cache_bytes_per_slot').read(run, Ctx()) \
        == 3 * 2 * 48 * 8 * 4
    for name in ('decode_state_updates', 'decode_state_tokens_folded'):
        assert run['registry'][name]['samples'][0]['value'] > 0
    for name in ('decode_kv_blocks_read', 'decode_context_positions_read'):
        samples = run['registry'].get(name, {}).get('samples') or []
        assert not samples or samples[0]['value'] == 0
    assert run['registry']['state_cache_rows_total']['samples'][0][
        'value'] == 4
    assert run['compiles']['window']['compiles'] == 0


def test_a_state_held_in_bfloat16_fails_by_the_states_own_limit(capsys):
    """The control of the configuration's `state_tolerance`
    (control_brumby.py, `state_bf16`), at the tiny size: the state rounded
    to bfloat16's 7 bits after every write. The rehearsal reads `correct`
    false by the state's limit (at float32 weights the logits see it too;
    on the chip, under bf16 weights, they do not: PERF.md section 6, PR
    30)."""
    import control_brumby
    restore = control_brumby.plant('state_bf16')
    try:
        harness = load('run.py', 'bench_run')
        harness.main(['--workload', 'tiny_serve_recurrent', '--seed', '77',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_recurrent',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['state_within_tolerance'] is False
    assert checks['every_answer_exact'] and checks['no_compile_in_window']
    assert min(max(e[1:]) for e in
               checks['state_err_prompt_len_folded_walked']) \
        > 10 * checks['state_tolerance']


def test_float8_feed_forward_weights_fail_by_the_logits_limit(capsys):
    """The control of `logit_tolerance` (control_brumby.py, `ffn_f8`) at the
    tiny size: the logits' limit fails and the first layer's state, which
    no feed-forward reaches, stays sound. (`state_one_pass` cannot be
    planted here: one pass is float32 on the CPU.)"""
    import control_brumby
    restore = control_brumby.plant('ffn_f8')
    try:
        harness = load('run.py', 'bench_run')
        harness.main(['--workload', 'tiny_serve_recurrent', '--seed', '78',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_recurrent',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['logits_within_tolerance'] is False
    assert checks['state_within_tolerance'] is True
    assert sorted(control_brumby.MODES) == ['ffn_f8', 'state_bf16',
                                            'state_one_pass']


def test_the_reference_states_the_first_layers_recurrence_without_phi():
    """reference.first_state against a float64 walk of the recurrence in
    index pairs, at the tiny size: M[a, b] = S[(a, b)] / c."""
    import numpy as np
    reference = load('reference/brumby_14b.py')
    with open(os.path.join(os.path.dirname(__file__), 'configs',
                           'tiny_brumby.json')) as f:
        config = json.load(f)
    m = reference.model_of(config)
    rng = np.random.default_rng(5)
    h, g, d = m['hidden_size'], m['num_key_value_heads'], m['head_dim']
    p = {'embed.weight': rng.standard_normal((m['vocab_size'], h)),
         'layers.0.norm1.weight': np.ones(h),
         'layers.0.attn.k_proj.weight': rng.standard_normal((h, g * d)) * .2,
         'layers.0.attn.v_proj.weight': rng.standard_normal((h, g * d)) * .2,
         'layers.0.attn.k_norm.weight': np.ones(d),
         'layers.0.attn.gate.weight': rng.standard_normal((h, g))}
    p = {k: v.astype('float32') for k, v in p.items()}
    tokens = rng.integers(1, m['vocab_size'], 11).tolist()
    got = np.asarray(reference.make_state(config, 16)(p, tokens))
    assert got.shape == (g, d, d, d + 1)
    # the walk: γ_t S + k kᵀ [v, 1], a token at a time, float64
    import jax.numpy as jnp
    x = jnp.asarray(p['embed.weight'][tokens])
    k, v, cum = reference._keys(p, 'layers.0.attn', m, reference._norm(
        x, p['layers.0.norm1.weight'], m['rms_norm_eps']))
    k, v = np.asarray(k, 'float64'), np.asarray(v, 'float64')
    gamma = np.exp(np.diff(np.asarray(cum, 'float64'), axis=0, prepend=0))
    want = np.zeros((g, d, d, d + 1))
    for t in range(len(tokens)):
        for j in range(g):
            want[j] = gamma[t, j] * want[j] + np.einsum(
                'a,b,c->abc', k[t, j], k[t, j], np.append(v[t, j], 1.0))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # padding after the sequence changes nothing
    np.testing.assert_array_equal(
        got, np.asarray(reference.make_state(config, 32)(p, tokens)))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference', 'brumby_14b.py')) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert 'paddle_tpu' not in code and 'import' in code
    assert 'HIGHEST' in source and 'float32' in source
    # the quadratic form with the cumulative gates: no chunk, no φ, no
    # carried state
    for word in ('chunk', 'phi', 'triu', 'roll'):
        assert word not in code, word
    assert 'cumsum' in code and 'causal' in code
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'brumby_14b.py'))


def test_the_runner_checks_three_rows_a_prompt():
    with open(os.path.join(BENCH, 'runners',
                           'serve_decode_recurrent.py')) as f:
        source = f.read()
    assert "base._logit_check = _logit_check" in source
    assert "check_decode_steps" in source
    assert "paddle_tpu" not in source.split('"""', 2)[2]
