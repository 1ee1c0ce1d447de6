"""The cell sdar_serve_saturated's own pieces (family sdar_30b_a3b, runner
serve_decode_diffusion, lib/block_read_ops.py and the four per-layer
readers) through the unedited harness at a tiny size on the CPU
(data/table_tiny_sdar.json), each reader on a run written by hand and on a
program that records none of it (the parent, on which the driver tries new
readers), the configuration's arithmetic, and the controls that must fail
(control_sdar.py). Entries of BENCHMARK.json are found by name, never
by place."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_sdar.json')
NEW = ['diffusion_tokens_per_slot_forward', 'diffusion_commit_forward_share',
       'block_read_time_share', 'block_read_roofline']
JOINED = ['serve_device_idle_share', 'serve_peak_hbm_gb',
          'kv_pool_fill_share', 'serve_mxu_time_share',
          'serve_compiles_in_window', 'decode_step_ms_p50',
          'prefill_time_share', 'slot_occupancy_mean', 'queue_wait_p50_ms',
          'serve_ttft_p50_ms', 'serve_itl_p90_ms', 'moe_experts_time_share',
          'moe_experts_roofline', 'expert_load_max_over_mean',
          'kv_cache_bytes_per_token']
CELL = 'sdar_serve_saturated'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def _config():
    with open(os.path.join(BENCH, 'configs', 'sdar_30b_a3b.json')) as f:
        return json.load(f)


def _traffic():
    with open(os.path.join(BENCH, 'traffic',
                           'closed_c128_ctx2k_steps2.json')) as f:
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
    assert entry['name'] == 'SDAR-30B-A3B-Chat'
    differs = {k for k, v in entry['config'].items() if config.get(k) != v}
    assert differs == set(config['reduced']) == {'num_hidden_layers'}
    assert config['published']['num_hidden_layers'] \
        == entry['config']['num_hidden_layers'] == 48
    assert config['num_hidden_layers'] == 6               # the floor is 4
    assert config['runner'] == 'serve_decode_diffusion'
    assert config['family'] == 'sdar_30b_a3b'
    for key in ('source', 'published', 'reduced', 'reduced_detail',
                'assumed', 'departures', 'deployment', 'dtype_policy'):
        assert config[key], key
    assert config['model']['block_length'] == 4
    assert config['model']['mask_token_id'] == 151669 < config['vocab_size']
    for key in ('block_length', 'mask_token_id', 'qk_norm', 'schedule',
                'commit', 'logit_shift', 'prompt_mask', 'initializer_range'):
        assert key in config['assumed'], key
    check = config['check']
    assert 0 < check['logit_tolerance'] < 0.1 and 0 < check['tie_margin']
    assert 'my chip runs, PR 32' in check['logit_tolerance_reason']
    assert 'my chip runs, PR 32' in check['tie_margin_reason']


def test_the_configurations_arithmetic_is_the_files():
    """4.361 B parameters, 8.72 GB of bf16 weights, 4.03 GB of K/V pool,
    12.75 GB resident: from the widths in the file and the traffic's
    engine."""
    c, engine = _config(), _traffic()['engine']
    h, d, v = c['hidden_size'], c['head_dim'], c['vocab_size']
    heads, groups = c['num_attention_heads'], c['num_key_value_heads']
    attention = 2 * h * heads * d + 2 * h * groups * d
    assert attention == 18874368                              # 18.87 M
    router = h * c['num_experts']
    experts = c['num_experts'] * 3 * h * c['moe_intermediate_size']
    assert (router, experts) == (262144, 603979776)
    layer = attention + router + experts + 2 * h + 2 * d     # and 4 norms
    parameters = c['num_hidden_layers'] * layer + 2 * v * h + h
    assert round(parameters / 1e9, 3) == 4.361
    weights = 2 * parameters
    assert round(weights / 1e9, 2) == 8.72
    assert round(7 * layer * 2 / 1e9 + 2 * v * h * 2 / 1e9, 2) == 9.97
    per_token = c['num_hidden_layers'] * 2 * groups * d * 2
    assert per_token == 12288
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert engine['max_blocks'] == engine['slots'] * per_slot + 8 == 20488
    pool = engine['max_blocks'] * engine['block_size'] * per_token
    assert round(pool / 1e9, 2) == 4.03
    assert round((weights + pool) / 1e9, 2) == 12.75
    assert f"{parameters / 1e9:.3f} B" in c['reduced_detail'][
        'num_hidden_layers']


def test_the_cell_is_sized_as_the_issue_says():
    traffic = _traffic()
    engine, load_ = traffic['engine'], traffic['load']
    assert traffic['runner'] == _config()['runner']
    assert (engine['slots'], load_['clients'], load_['loop']) \
        == (128, 128, 'closed')
    assert engine['prompt_buckets'] == [128, 256, 512, 1024, 2048]
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] \
        == engine['max_prompt_len']
    assert load_['prompt_len'] == {'median': 256, 'sigma': 0.9, 'min': 64,
                                   'max': 2048}
    assert load_['output_len'] == {'median': 256, 'sigma': 0.5, 'min': 64,
                                   'max': 512}
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    # ids below MASK: a prompt never holds the mask token
    assert load_['vocab'] == _config()['model']['mask_token_id']
    assert engine['block_size'] % _config()['model']['block_length'] == 0
    assert (engine['kv_dtype'], engine['denoising_steps']) == ('bf16', 2)
    assert not (engine['prefix_cache'] or engine['spec_decode']
                or engine['disagg'])
    assert (traffic['check_prompts'], traffic['check_blocks']) == (4, 16)
    # the check's table fits a slot's reservation
    assert (traffic['check_blocks'] + 2) * 4 <= engine['max_new_tokens_cap']


def test_the_entries_are_appended_and_the_cell_joins_the_lists_by_name():
    tab = table()
    cell = next(w for w in tab['workloads'] if w['name'] == CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        'sdar_30b_a3b', 'closed_c128_ctx2k_steps2', 1)
    config = next(c for c in tab['configs'] if c['name'] == 'sdar_30b_a3b')
    assert config['file'] == 'benchmark/configs/sdar_30b_a3b.json'
    assert config['reduced'] == ['num_hidden_layers'] == _config()['reduced']
    assert config['source'] == _config()['source']
    per_layer = {m['name']: m for m in tab['per_layer']}
    for name in NEW:
        m = per_layer[name]
        assert m['workloads'] == [CELL] and m['moves'] == 'serve_tokens_per_s'
        assert m['layer'] == _reader(name).LAYER
        assert m['unit'] == _reader(name).UNIT
    for name in JOINED:
        assert per_layer[name]['workloads'][-1] == CELL
    # a block's tokens arrive together: the median gap is zero by
    # construction; and PR 24's eight stay with their two cells
    assert CELL not in per_layer['serve_itl_p50_ms']['workloads']
    for name in ('engine_forward_share', 'scheduler_self_share',
                 'serve_idle_unattributed_share'):
        assert CELL not in per_layer[name]['workloads']
    e2e = {m['name']: m for m in tab['end_to_end']}
    assert e2e['serve_tokens_per_s']['workloads'][-1] == CELL
    names = [m['name'] for m in tab['per_layer']]
    assert max(names.index(n) for n in JOINED) < min(
        names.index(n) for n in NEW)
    assert names.index('state_cache_bytes_per_slot') < names.index(NEW[0])
    assert [w['name'] for w in tab['workloads']][-1] == CELL
    assert len(tab['workloads']) == 7
    for entry in (cell, config):
        assert len(entry['why']) <= 200


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/sdar_30b_a3b.py')
    config = _config()
    # a step: 512 rows x 8 over 6 layers, all 128 experts of each touched
    f, b = flops.experts(config, 512 * 8 * 6, 128 * 6)
    assert f == 512 * 8 * 6 * 6 * 2048 * 768
    assert b == 128 * 6 * 3 * 2048 * 768 * 2 + 512 * 8 * 6 * 2 * 2048 * 2
    assert 128 * 6 * 3 * 2048 * 768 * 2 == 7247757312       # 7.25 GB a step
    assert b / 819e9 > f / 197e12                           # bytes bind
    # the block read: K and V rows of 4 heads of 128 in bf16 a position a
    # layer; 32 heads x 4 rows of a score and a weighted sum over 128
    f, b = flops.block_read(config, 1000)
    assert b == 1000 * 2048 and f == 1000 * 32 * 4 * 4 * 128
    assert b / 819e9 > f / 197e12


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


def test_block_read_time_and_roofline_over_the_slices_own_steps(monkeypatch):
    from paddle_tpu import observability as obs
    scope = 'jit(run)/jit(main)/kv/block_read/jit(call_with)/'
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            (scope + 'while/body/dot_general', 1.5, 1.9),
            (scope + 'while/body/gather', 0.8, 1.1),            # cut at 1
            ('jit(run)/jit(main)/moe/experts/ragged_dot', 2.0, 2.3),
            ('jit(run)/jit(main)/kv/decode_read/dot_general', 3.0, 3.3)], [
            ('engine/step', 2.5, dict(window=4, context_positions=4 * 10 ** 8,
                                      slot_forwards=128, commits=40)),
            ('engine/step', 3.0, dict(window=4, context_positions=10 ** 8)),
            ('engine/step', 3.5, dict(context_positions=10 ** 9)),  # window 1
            ('engine/prefill', 4.0, dict(prompt_len=900, bucket=1024)),
            ('engine/step', 6.5, dict(window=4,                  # outside
                                      context_positions=10 ** 9)),
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in NEW[2:]}
        finally:
            obs.reset()
    found = run['block_read_ops']
    assert found['steps'] == 2 and found['positions'] == 5 * 10 ** 8
    assert found['seconds'] == pytest.approx(0.5)
    assert values['block_read_time_share'] == pytest.approx(25.0)
    assert values['block_read_roofline'] == pytest.approx(
        100 * (5e8 * 2048 / 819e9) / 0.5)
    assert all(0 < v for v in values.values())


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}},
    {'registry': {'decode_diffusion_commit_forwards':
                  {'type': 'counter', 'samples': []}}, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    run.setdefault('counts', {})
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_trace_readers_find_nothing_where_the_program_names_no_scope(
        monkeypatch):
    """The parent's traced run, or another model's: a device trace, no such
    scope, no window in the spans' args."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/kv/decode_read/dot_general', 1.5, 2.5)], [
            ('engine/step', 2.5, dict(context_positions=10 ** 6))])
        try:
            for name in NEW[2:]:
                assert _reader(name).read(run, Ctx()) is None, name
        finally:
            obs.reset()
    assert run['block_read_ops']['steps'] == 0


def test_registry_readers_on_a_run_written_by_hand():
    def counter(value):
        return {'type': 'counter', 'samples': [{'labels': {},
                                                'value': value}]}
    run = {'counts': {}, 'registry': {
        'decode_diffusion_tokens_committed': counter(2560),
        'decode_diffusion_denoise_forwards': counter(1300),
        'decode_diffusion_commit_forwards': counter(650)}}
    assert _reader('diffusion_tokens_per_slot_forward').read(run, Ctx()) \
        == pytest.approx(2560 / 1950)
    assert _reader('diffusion_commit_forward_share').read(run, Ctx()) \
        == pytest.approx(100 / 3)


def _rehearse(capsys, seed, trace=0, seconds='1'):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_diffusion', '--seed',
                       str(seed), '--seconds', seconds, '--trace',
                       str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    with open(os.path.join(BENCH, 'out', 'tiny_serve_diffusion',
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
    # the shortest prompt, a draw, the longest; per prompt its length, the
    # first block's first forward, the later block's second
    assert [e[0] for e in errors][::2] == [5, 16] and len(errors) == 3
    assert all(len(e) == 3 and 0 < max(e[1:]) < 1e-4 for e in errors)
    assert run['checks']['every_answer_exact'] is True
    if not trace:
        assert set(last['metrics']) == {'serve_tokens_per_s', 'setup_s'}
        return
    # off a TPU there is no device plane: the trace readers are left out,
    # the counters' are there (null off the chip: not counts)
    assert set(NEW[:2]) <= set(last['metrics'])
    assert not set(NEW[2:]) & set(last['metrics'])
    assert set(JOINED) - {
        'serve_device_idle_share', 'serve_mxu_time_share',
        'serve_peak_hbm_gb', 'moe_experts_time_share',
        'moe_experts_roofline'} <= set(last['metrics'])
    assert 'serve_itl_p50_ms' not in last['metrics']
    ratio = _reader('diffusion_tokens_per_slot_forward').read(run, Ctx())
    share = _reader('diffusion_commit_forward_share').read(run, Ctx())
    # 2 steps a block of 4: at most 4/3 tokens a slot-forward, a commit in
    # about three forwards (short answers, cut last blocks: well below)
    assert 0.3 < ratio <= 4 / 3 and 30 < share < 55
    registry = run['registry']
    for name in ('decode_diffusion_denoise_forwards',
                 'decode_diffusion_commit_forwards',
                 'decode_diffusion_tokens_committed',
                 'decode_expert_assignments',
                 'decode_context_positions_read', 'decode_kv_blocks_read'):
        assert registry[name]['samples'][0]['value'] > 0, name
    committed = registry['decode_diffusion_tokens_committed']['samples'][0][
        'value']
    assert committed == registry['decode_tokens_generated']['samples'][0][
        'value']
    assert sum(s['count'] for s in
               registry['decode_block_seconds']['samples']) \
        == registry['decode_diffusion_commit_forwards']['samples'][0]['value']
    assert _reader('kv_cache_bytes_per_token').read(run, type(
        'C', (Ctx,), {'traffic': {'engine': {'block_size': 4}}})()) \
        == 3 * 2 * 128 * 4
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) >= 1.0
    assert run['compiles']['window']['compiles'] == 0


@pytest.mark.parametrize('mode,first_sound', [('weights_f8', False),
                                              ('experts_f8', False),
                                              ('causal_reference', False),
                                              ('skip_commit', True)])
def test_each_control_reads_correct_false(capsys, mode, first_sound):
    """The controls of the configuration's `check` (control_sdar.py) at the
    tiny size, in float32: each reads `correct` false by the logits' limit
    with every answer exact (on the chip, under bf16, the experts' weights
    alone in float8 do not: the configuration's `check` says so); with
    commits skipped the first block's forward, over the prefill's K/V alone,
    stays sound."""
    import control_sdar
    harness = load('run.py', 'bench_run')
    restore = control_sdar.plant(mode, harness)
    try:
        harness.main(['--workload', 'tiny_serve_diffusion', '--seed', '91',
                      '--seconds', '0.5', '--trace', '0'], rehearsal=True,
                     table=TABLE)
    finally:
        restore()
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last['correct'] is False and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_diffusion',
                           'last_run.json')) as f:
        checks = json.load(f)['run']['checks']
    assert checks['logits_within_tolerance'] is False
    assert checks['every_answer_exact'] and checks['no_compile_in_window']
    errors = checks['logit_err_prompt_len_prefill_decode']
    tolerance = checks['logit_tolerance']
    assert min(e[2] for e in errors) > 10 * tolerance
    if first_sound:
        assert max(e[1] for e in errors) < tolerance
    assert sorted(control_sdar.MODES) == ['causal_reference', 'experts_f8',
                                          'skip_commit', 'weights_f8']


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference', 'sdar_30b_a3b.py')) as f:
        source = f.read()
    assert 'paddle_tpu' not in source.split('"""', 2)[2]
    assert 'HIGHEST' in source and 'float32' in source
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'sdar_30b_a3b.py'))
