"""The cell trinity_serve_saturated's own pieces (family
trinity_large_preview, runner serve_decode_sliding, lib/layer_class_ops.py
and the eight per-layer readers) through the unedited harness at a tiny size
on the CPU (data/table_tiny_trinity.json), each reader on a run written by
hand and on a program that records none of it (the parent, on which the
driver tries new readers), the configuration's arithmetic, and the controls
that must fail (control_trinity.py). Entries of BENCHMARK.json are found by
name: this file holds no place and no count, so that the next cell reddens
nothing here."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_trinity.json')
TRACE_READERS = ['sliding_read_time_share', 'sliding_read_roofline',
                 'kv_decode_read_time_share', 'kv_decode_read_roofline',
                 'prefill_attention_time_share',
                 'prefill_attention_roofline']
COUNTER_READERS = ['kv_span_saved_share', 'expert_held_assignment_share']
NEW = TRACE_READERS + COUNTER_READERS
JOINED = ['serve_device_idle_share', 'serve_peak_hbm_gb',
          'kv_pool_fill_share', 'serve_mxu_time_share',
          'serve_compiles_in_window', 'decode_step_ms_p50',
          'prefill_time_share', 'slot_occupancy_mean', 'queue_wait_p50_ms',
          'serve_ttft_p50_ms', 'serve_itl_p50_ms', 'serve_itl_p90_ms',
          'moe_experts_time_share', 'moe_experts_roofline',
          'expert_load_max_over_mean']
CELL = 'trinity_serve_saturated'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
REDUCED = {'num_hidden_layers', 'num_dense_layers', 'layer_types',
           'num_experts', 'vocab_size'}


def _config():
    with open(os.path.join(BENCH, 'configs',
                           'trinity_large_preview.json')) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(BENCH, 'traffic', 'closed_c24_ctx16k.json')) as f:
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
    under its own name; only the five cut keys differ, and say so; no width
    among them."""
    config = _config()
    if not os.path.exists(CATALOG):
        pytest.skip('no model-configs catalog on this machine')
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e['source_url'] == config['source'])
    assert entry['name'] == 'Trinity-Large-Preview'
    differs = {k for k, v in entry['config'].items() if config.get(k) != v}
    assert differs == set(config['reduced']) == REDUCED
    published = entry['config']
    for key in ('num_hidden_layers', 'num_dense_layers', 'num_experts',
                'vocab_size'):
        assert config['published'][key] == published[key], key
    assert published['layer_types'].count('sliding_attention') == 45
    # the kept layers: published 0 and 8-11, a whole period after the dense
    assert config['layer_types'] == [published['layer_types'][i]
                                     for i in (0, 8, 9, 10, 11)]
    assert (config['num_hidden_layers'], config['num_dense_layers']) == (5, 1)
    assert config['experts_held'] == [0, 32] and config['num_experts'] == 32
    assert config['router_width'] == published['num_experts'] == 256
    assert config['vocab_size'] * 8 == published['vocab_size']
    # no width is cut
    for key in ('hidden_size', 'head_dim', 'num_attention_heads',
                'num_key_value_heads', 'intermediate_size',
                'moe_intermediate_size', 'sliding_window',
                'num_experts_per_tok', 'route_scale'):
        assert config[key] == published[key], key
    assert (config['runner'], config['family']) == (
        'serve_decode_sliding', 'trinity_large_preview')
    for key in ('source', 'published', 'reduced', 'reduced_detail',
                'assumed', 'departures', 'deployment', 'dtype_policy'):
        assert config[key], key
    for key in ('norms', 'gate', 'qk_norm', 'rope', 'embedding', 'router',
                'initializer_range', 'block_size'):
        assert key in config['assumed'], key
    assert 'not held against' in config['assumed'][
        'what config.json does not carry'].lower()
    assert '8 chips' in config['deployment']
    check = config['check']
    assert 0 < check['logit_tolerance'] < 0.1 and 0 < check['tie_margin']
    assert 'my chip runs, PR 36' in check['logit_tolerance_reason']
    assert 'my chip runs, PR 36' in check['tie_margin_reason']


def test_the_configurations_arithmetic_is_the_files():
    """4.322 B parameters, 8.64 GB of bf16 weights, 1.66 + 1.62 GB of K/V
    pool in its two classes, 11.92 GB resident: from the widths in the file
    and the traffic's engine."""
    c, engine = _config(), _traffic()['engine']
    h, d, v = c['hidden_size'], c['head_dim'], c['vocab_size']
    heads, groups = c['num_attention_heads'], c['num_key_value_heads']
    attention = 3 * h * heads * d + 2 * h * groups * d     # q, o, gate; k, v
    assert attention == 62914560                              # 62.915 M
    dense = 3 * h * c['intermediate_size']
    assert dense == 113246208
    expert = 3 * h * c['moe_intermediate_size']
    assert expert == 28311552
    router = h * c['router_width']
    sparse = attention + router + c['num_shared_experts'] * expert \
        + c['num_experts'] * expert
    assert round(sparse / 1e6, 1) == 998.0
    assert round(c['published']['num_experts'] * expert / 1e9, 2) == 7.25
    norms = 4 * h + 2 * d
    parameters = (attention + dense + norms) + 4 * (sparse + norms) \
        + 2 * v * h + h
    assert round(parameters / 1e9, 3) == 4.322
    weights = 2 * parameters
    assert round(weights / 1e9, 2) == 8.64
    block = engine['block_size'] * 2 * groups * d * 2
    assert block == 65536
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert engine['max_blocks'] == engine['slots'] * per_slot + 8 == 25352
    sliding_layers = c['layer_types'].count('sliding_attention')
    ring = c['sliding_window'] // engine['block_size'] + 1
    sliding_blocks = engine['slots'] * ring + 8
    assert (sliding_layers, ring, sliding_blocks) == (4, 257, 6176)
    full = (5 - sliding_layers) * engine['max_blocks'] * block
    sliding = sliding_layers * sliding_blocks * block
    assert (round(full / 1e9, 2), round(sliding / 1e9, 2)) == (1.66, 1.62)
    assert round((weights + full + sliding) / 1e9, 2) == 11.92
    # every layer full: the cell would not load
    assert round((weights + 5 * engine['max_blocks'] * block) / 1e9,
                 2) == 16.95
    detail = c['reduced_detail']
    assert '4.322 B' in detail['num_hidden_layers']
    assert '8.64 GB' in detail['num_hidden_layers']
    assert '1.66 GB' in detail['kv_pool'] and '1.62 GB' in detail['kv_pool']
    assert '11.92 GB' in detail['kv_pool']


def test_the_engine_derives_the_sliding_classes_depth():
    from paddle_tpu.serving.decode.engine import SLIDING_SPARE_BLOCKS
    engine = _traffic()['engine']
    assert 'sliding_blocks' not in engine and 'span' not in engine
    assert SLIDING_SPARE_BLOCKS == 8


def test_the_cell_is_sized_as_the_issue_says():
    traffic = _traffic()
    engine, load_ = traffic['engine'], traffic['load']
    assert traffic['runner'] == _config()['runner']
    assert (engine['slots'], load_['clients'], load_['loop']) \
        == (24, 24, 'closed')
    assert engine['prompt_buckets'] == [512, 1024, 2048, 4096, 8192, 16384]
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] \
        == engine['max_prompt_len']
    assert load_['prompt_len'] == {'median': 6144, 'sigma': 0.7, 'min': 512,
                                   'max': 16384}
    assert load_['output_len'] == {'median': 256, 'sigma': 0.5, 'min': 64,
                                   'max': 512}
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    assert load_['vocab'] == _config()['vocab_size']
    assert engine['kv_dtype'] == 'bf16' and engine['block_size'] == 16
    assert not (engine['prefix_cache'] or engine['spec_decode']
                or engine['disagg'])
    assert (traffic['check_prompts'], traffic['check_steps']) == (4, 16)
    window = _config()['sliding_window']
    # decoding crosses the window's edge inside the check
    assert traffic['check_edge_prompt'] < window \
        < traffic['check_edge_prompt'] + traffic['check_steps']


def test_the_entries_are_found_by_name_and_the_cell_joins_the_lists():
    tab = table()
    cell = next(w for w in tab['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'trinity_large_preview', 'closed_c24_ctx16k', 1)
    config = next(c for c in tab['configs']
                  if c['name'] == 'trinity_large_preview')
    assert config['file'] == 'benchmark/configs/trinity_large_preview.json'
    assert set(config['reduced']) == REDUCED
    assert config['reduced'] == _config()['reduced']
    assert config['source'] == _config()['source']
    per_layer = {m['name']: m for m in tab['per_layer']}
    for name in NEW:
        m = per_layer[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'serve_tokens_per_s'
        assert m['layer'] == _reader(name).LAYER
        assert m['unit'] == _reader(name).UNIT == '%'
    assert {per_layer[n]['source'] for n in TRACE_READERS} == {'device_trace'}
    assert {per_layer[n]['source'] for n in COUNTER_READERS} == {
        'program_counter'}
    for name in JOINED:
        assert CELL in per_layer[name]['workloads'], name
    # no one number a token where a layer's bytes stop at its span; PR 24's
    # eight and PR 34's five stay with the cells their tests pin them to
    for name in ('kv_cache_bytes_per_token', 'engine_forward_share',
                 'scheduler_self_share', 'serve_idle_unattributed_share',
                 'worker_on_cpu_share', 'http_handler_cpu_share'):
        assert CELL not in per_layer[name]['workloads'], name
    e2e = {m['name']: m for m in tab['end_to_end']}
    assert CELL in e2e['serve_tokens_per_s']['workloads']
    for entry in (cell, config):
        assert len(entry['why']) <= 200


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/trinity_large_preview.py')
    config = _config()
    h = f = 3072
    # a step: 24 rows x 4 over 4 expert layers, an eighth of them held
    got = flops.experts(config, 12, 10)
    assert got == (12 * 6 * h * f, 10 * 3 * h * f * 2 + 12 * 2 * h * 2)
    assert 3 * h * f * 2 == 56623104            # 56.6 MB an expert touched
    assert got[1] / 819e9 > got[0] / 197e12     # bytes bind a step
    # a read: K and V rows of 8 heads of 128 in bf16 a position a layer; 48
    # heads of a score and a weighted sum over 128
    for read in (flops.decode_read, flops.sliding_read):
        fl, by = read(config, 1000)
        assert by == 1000 * 4096 and fl == 1000 * 48 * 4 * 128
        assert by / 819e9 > fl / 197e12
    assert flops.layer_counts(config) == (1, 4)
    # the masks: a prompt inside the window is causal in both classes
    assert flops.visible_pairs(10) == flops.visible_pairs(10, 4096) == 55
    assert flops.visible_pairs(4096, 4096) == 4096 * 4097 // 2
    assert flops.visible_pairs(6, 4) == 1 + 2 + 3 + 4 + 4 + 4
    fl, by = flops.prefill_attention(config, [6144, 512])
    pairs = (6144 * 6145 // 2 + 4 * (4096 * 4097 // 2 + 2048 * 4096)
             + 5 * (512 * 513 // 2))
    assert fl == pairs * 48 * 4 * 128
    assert by == (6144 + 512) * 5 * 2 * (48 + 8) * 128 * 2
    assert fl / 197e12 > by / 819e9             # FLOPs bind a prefill


def _planes(ops):
    """A decoded trace: marks at 1 s and 5 s on the trace's clock (2 s and
    6 s on perf_counter), and chip 0's ops as (tf_op, start_s, end_s)."""
    xplane = Ctx.xplane
    host = {'name': '/host:CPU', 'lines': {'python3': [
        (xplane.mark_name('begin', int(2e9)), int(1e12), int(1e12), {}),
        (xplane.mark_name('end', int(6e9)), int(5e12), int(5e12), {})]}}
    device = {'name': '/device:TPU:0', 'lines': {'XLA Ops': [
        ('%fusion' if name else '%vmap_jit__splash_attention__.7',
         int(a * 1e12), int(b * 1e12), {'tf_op': name})
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
            (at + 'kv/sliding_read/jit(call_with)/while/body/dot', 1.5, 1.9),
            (at + 'kv/sliding_read/jit(call_with)/gather', 0.8, 1.1),  # cut
            (at + 'kv/decode_read/jit(call_with)/while/body/dot', 2.0, 2.2),
            (at + 'attn/full_prefill/jit(call_with)/while/dot', 3.0, 3.1),
            (at + 'attn/sliding_prefill/jit(call_with)/while/dot', 3.1, 3.4),
            ('', 3.4, 3.5),                  # the splash kernel: no op_name
            (at + 'attn/gate/mul', 3.5, 3.6),
            (at + 'moe/experts/pallas_call', 4.0, 4.3)], [
            ('engine/step', 2.5, dict(full_positions=10 ** 8,
                                      sliding_positions=3 * 10 ** 8)),
            ('engine/step', 3.0, dict(full_positions=10 ** 8,
                                      sliding_positions=10 ** 8)),
            ('engine/step', 3.5, dict(context_positions=10 ** 9)),  # other
            ('engine/prefill', 4.0, dict(prompt_len=6144, bucket=8192,
                                         full_positions=6144,
                                         sliding_positions=16384)),
            ('engine/prefill', 6.5, dict(prompt_len=900, bucket=1024,
                                         full_positions=900)),    # outside
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in TRACE_READERS}
        finally:
            obs.reset()
    found = run['layer_class_ops']
    assert found['work'] == {'full_positions': 2 * 10 ** 8,
                             'sliding_positions': 4 * 10 ** 8,
                             'prompt_lens': [6144], 'steps': 2}
    assert found['scopes'] == pytest.approx({
        'kv/sliding_read': 0.5, 'kv/decode_read': 0.2,
        'attn/full_prefill': 0.1, 'attn/sliding_prefill': 0.3,
        'attn/prefill_kernel': 0.1})
    assert values['sliding_read_time_share'] == pytest.approx(25.0)
    assert values['kv_decode_read_time_share'] == pytest.approx(10.0)
    assert values['prefill_attention_time_share'] == pytest.approx(25.0)
    assert values['sliding_read_roofline'] == pytest.approx(
        100 * (4e8 * 4096 / 819e9) / 0.5)
    assert values['kv_decode_read_roofline'] == pytest.approx(
        100 * (2e8 * 4096 / 819e9) / 0.2)
    flops = load('flops/trinity_large_preview.py').prefill_attention(
        _config(), [6144])[0]
    assert values['prefill_attention_roofline'] == pytest.approx(
        100 * (flops / 197e12) / 0.5)
    assert all(0 < v for v in values.values())


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}},
    {'registry': {'decode_kv_positions_held':
                  {'type': 'counter', 'samples': []}}, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    run.setdefault('counts', {})
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_trace_readers_find_nothing_where_the_program_names_no_class(
        monkeypatch):
    """The parent's traced run, or another model's: a device trace, a
    `kv/decode_read` scope of a pool of one class, no class in the spans'
    args. Only the time share of that scope reads (GPT-1's read has the
    same name); no roofline, for want of the class's positions."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/kv/decode_read/dot_general', 1.5, 2.5),
            ('jit(run)/kv/block_read/dot_general', 2.5, 3.0)], [
            ('engine/step', 2.5, dict(context_positions=10 ** 6)),
            ('engine/prefill', 3.0, dict(prompt_len=100, bucket=128))])
        try:
            got = {n: _reader(n).read(run, Ctx()) for n in TRACE_READERS}
        finally:
            obs.reset()
    assert got.pop('kv_decode_read_time_share') == pytest.approx(50.0)
    assert set(got.values()) == {None}
    assert run['layer_class_ops']['work']['steps'] == 0


def test_registry_readers_on_a_run_written_by_hand():
    def counter(value):
        return {'type': 'counter', 'samples': [{'labels': {},
                                                'value': value}]}
    run = {'counts': {}, 'registry': {
        'decode_kv_positions_held': counter(600),
        'decode_kv_positions_if_unwindowed': counter(1000),
        'decode_expert_assignments_held': counter(125),
        'decode_expert_assignments_total': counter(1000)}}
    assert _reader('kv_span_saved_share').read(run, Ctx()) \
        == pytest.approx(40.0)
    assert _reader('expert_held_assignment_share').read(run, Ctx()) \
        == pytest.approx(12.5)


def _rehearse(capsys, seed, trace=0, seconds='1'):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_sliding', '--seed',
                       str(seed), '--seconds', seconds, '--trace',
                       str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    with open(os.path.join(BENCH, 'out', 'tiny_serve_sliding',
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
    # the shortest prompt, the longest, the one at the window's edge, a
    # draw; per prompt its length, the prefill's row, step 1, step 6
    assert [e[0] for e in errors][:3] == [3, 32, 6] and len(errors) == 4
    assert all(len(e) == 4 and 0 < max(e[1:]) < 1e-4 for e in errors)
    assert run['checks']['every_answer_exact'] is True
    if not trace:
        assert set(last['metrics']) == {'serve_tokens_per_s', 'setup_s'}
        return
    # off a TPU there is no device plane: the trace readers are left out,
    # the counters' are there (null off the chip: not counts)
    assert set(COUNTER_READERS) <= set(last['metrics'])
    assert not set(TRACE_READERS) & set(last['metrics'])
    assert set(JOINED) - {
        'serve_device_idle_share', 'serve_mxu_time_share',
        'serve_peak_hbm_gb', 'moe_experts_time_share',
        'moe_experts_roofline'} <= set(last['metrics'])
    saved = _reader('kv_span_saved_share').read(run, Ctx())
    share = _reader('expert_held_assignment_share').read(run, Ctx())
    # prompts up to 32 over a window of 8 in 3 of 4 layers; 4 of the
    # router's 8 experts held
    assert 5 < saved < 75 and 30 < share < 70
    registry = run['registry']
    for name in ('decode_kv_positions_held',
                 'decode_kv_positions_if_unwindowed',
                 'decode_expert_assignments_total',
                 'decode_expert_assignments_held',
                 'decode_expert_assignments',
                 'decode_context_positions_read', 'decode_kv_blocks_read'):
        assert registry[name]['samples'][0]['value'] > 0, name
    value = lambda n: registry[n]['samples'][0]['value']
    assert value('decode_expert_assignments') \
        == value('decode_expert_assignments_held')
    assert value('decode_kv_positions_held') \
        < value('decode_kv_positions_if_unwindowed')
    for name in ('decode_full_blocks_held', 'decode_sliding_blocks_held'):
        assert name in registry, name
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) >= 1.0
    assert _reader('kv_pool_fill_share').read(run, Ctx()) > 0
    assert run['compiles']['window']['compiles'] == 0


@pytest.mark.parametrize('mode,sound_inside', [('weights_f8', False),
                                               ('full_reference', True),
                                               ('rope_everywhere', False),
                                               ('span_short', True)])
def test_each_control_reads_correct_false(capsys, monkeypatch, mode,
                                          sound_inside):
    """The controls of the configuration's `check` (control_trinity.py) at
    the tiny size, in float32: each reads `correct` false by the logits'
    limit with every answer exact. The window's two controls cannot show on
    a prompt that stays inside the (shorter) window: the shortest prompt's
    rows stay sound, the longest's are far outside."""
    import control_trinity
    monkeypatch.setattr(control_trinity, 'SPAN_SHORT_BY', 4)
    harness = load('run.py', 'bench_run')
    restore = control_trinity.plant(mode, harness)
    try:
        harness.main(['--workload', 'tiny_serve_sliding', '--seed', '91',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_sliding',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['logits_within_tolerance'] is False
    assert checks['every_answer_exact'] and checks['no_compile_in_window']
    errors = {e[0]: e[1:] for e in
              checks['logit_err_prompt_len_prefill_decode']}
    tolerance = checks['logit_tolerance']
    assert max(errors[32]) > 10 * tolerance
    if sound_inside:
        assert max(errors[3][:2]) < tolerance
    else:
        assert min(min(e) for e in errors.values()) > 10 * tolerance
    assert sorted(control_trinity.MODES) == [
        'full_reference', 'rope_everywhere', 'span_short', 'weights_f8']


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference',
                           'trinity_large_preview.py')) as f:
        source = f.read()
    assert 'paddle_tpu' not in source.split('"""', 2)[2]
    assert 'HIGHEST' in source and 'float32' in source
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'trinity_large_preview.py'))
