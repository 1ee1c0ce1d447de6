"""The cell kanana2_serve_saturated's own pieces (family kanana2_30b_a3b,
runner serve_decode_routed, lib/scoped_ops.py and the six per-layer readers)
through the unedited harness at a tiny size on the CPU
(data/table_tiny_kanana.json), and each reader on a run written by hand and
on a program that records none of it (the parent, on which the driver tries
new readers)."""
import json
import os

import pytest

from bench_testlib import BENCH, DATA, REPO, load, table

TABLE = os.path.join(DATA, 'table_tiny_kanana.json')
NEW = ['moe_experts_time_share', 'moe_experts_roofline',
       'mla_decode_read_time_share', 'mla_decode_read_roofline',
       'expert_load_max_over_mean', 'kv_cache_bytes_per_token']
SERVE = ['serve_device_idle_share', 'serve_peak_hbm_gb', 'kv_pool_fill_share',
         'serve_mxu_time_share', 'serve_compiles_in_window',
         'decode_step_ms_p50', 'prefill_time_share', 'slot_occupancy_mean',
         'queue_wait_p50_ms', 'serve_ttft_p50_ms', 'serve_itl_p50_ms',
         'serve_itl_p90_ms']
CELL = 'kanana2_serve_saturated'


def _config():
    with open(os.path.join(BENCH, 'configs', 'kanana2_30b_a3b.json')) as f:
        return json.load(f)


class Ctx:
    """What a reader asks of the harness's Context."""
    stats = load('lib/stats.py')
    xplane = load('lib/xplane.py')
    config = _config()
    traffic = {'engine': {'block_size': 16}}
    trace_file = 'a.xplane.pb'

    def module(self, kind, name):
        return load(f'{kind}/{name}.py')


def _reader(name):
    return load(f'layer_metrics/{name}.py')


def test_the_configuration_is_the_catalog_entry_but_for_its_depth():
    """Every key of the published config.json at the top level of the file,
    under its own name; only num_hidden_layers differs, and says so."""
    config = _config()
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no model-configs catalog on this machine')
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e['source_url'] == config['source'])
    differs = {k for k, v in entry['config'].items() if config.get(k) != v}
    assert differs == set(config['reduced']) == {'num_hidden_layers'}
    assert config['published']['num_hidden_layers'] \
        == entry['config']['num_hidden_layers'] == 48
    assert config['num_hidden_layers'] == 6
    assert config['n_routed_experts'] == 128
    assert config['num_experts_per_tok'] == 6
    assert config['n_shared_experts'] == 2 and config['vocab_size'] == 128256


def test_the_cell_is_sized_as_the_issue_says():
    with open(os.path.join(BENCH, 'traffic', 'closed_c128_ctx4k.json')) as f:
        traffic = json.load(f)
    engine, load_ = traffic['engine'], traffic['load']
    per_slot = -(-(engine['max_prompt_len'] + engine['max_new_tokens_cap'])
                 // engine['block_size'])
    assert engine['max_blocks'] == engine['slots'] * per_slot + 8 == 35848
    assert engine['prompt_buckets'][-1] == load_['prompt_len']['max'] == 4096
    assert load_['output_len']['max'] == engine['max_new_tokens_cap']
    assert load_['vocab'] == _config()['vocab_size']
    assert engine['kv_dtype'] == 'bf16' and load_['clients'] == 128
    # 576 bf16 values a token a layer over 6 layers, in 640 lanes
    config = _config()
    row = (config['kv_lora_rank'] + config['qk_rope_head_dim']) * 2
    assert row * config['num_hidden_layers'] == 6912
    assert 640 * 2 * config['num_hidden_layers'] == 7680


def test_the_entries_end_the_table_and_the_cell_joins_the_lists():
    """The six entries are appended, as the builder's contract has every
    new entry (the driver refused them before PR 24's eight, where ISSUE 26
    section 7 put them); PR 24's eight come just before, as they were and
    for the GPT-1 cell alone."""
    per_layer = table()['per_layer']
    names = [m['name'] for m in per_layer]
    assert names[-6:] == NEW
    assert all(m['workloads'] == ['gpt1_serve_saturated']
               for m in per_layer[-14:-6])
    for m in per_layer:
        if m['name'] in NEW:
            assert m['workloads'] == [CELL]
            assert m['moves'] == 'serve_tokens_per_s'
        elif m['name'] in SERVE:
            assert m['workloads'] == ['gpt1_serve_saturated', CELL]
    e2e = {m['name']: m for m in table()['end_to_end']}
    assert e2e['serve_tokens_per_s']['workloads'][-1] == CELL


def test_flops_count_the_work_the_mathematics_needs():
    flops = load('flops/kanana2_30b_a3b.py')
    config = _config()
    # a step: 128 tokens x 6 over 5 layers, all 128 experts of each touched
    f, b = flops.experts(config, 128 * 6 * 5, 128 * 5)
    assert f == 128 * 6 * 5 * 6 * 2048 * 768
    assert b == 128 * 5 * 3 * 2048 * 768 * 2 + 128 * 6 * 5 * 2 * 2048 * 2
    assert 3 * 2048 * 768 * 2 == 9437184                   # 9.44 MB an expert
    f, b = flops.decode_read(config, 1000)
    assert b == 1000 * 1152 and f == 1000 * 32 * 2 * (576 + 512)


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
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [
            ('jit(run)/jit(main)/moe/experts/ragged_dot', 1.5, 1.9),
            ('jit(run)/jit(main)/moe/experts/sort', 0.8, 1.1),   # cut at 1
            ('jit(run)/jit(main)/moe/shared/dot_general', 2.0, 2.3),
            ('jit(run)/jit(main)/mla/decode_read/gather', 3.0, 3.2),
            ('jit(run)/jit(main)/mla/decode_read/dot_general', 4.9, 5.4),
            ('jit(run)/jit(main)/dot_general', 3.5, 3.6)], [
            ('engine/step', 2.5, dict(expert_assignments=3840,
                                      experts_touched=640,
                                      context_positions=10 ** 8)),
            ('engine/prefill', 3.5, dict(expert_assignments=5120,
                                         experts_touched=600)),
            ('engine/step', 6.5, dict(expert_assignments=3840,   # outside
                                      experts_touched=640,
                                      context_positions=10 ** 9)),
            ('engine/step/forward', 2.5, {})])
        try:
            values = {n: _reader(n).read(run, Ctx()) for n in NEW[:4]}
        finally:
            obs.reset()
    found = run['scoped_ops']
    assert found['calls'] == 2
    assert found['work'] == {'expert_assignments': 8960,
                             'experts_touched': 1240,
                             'context_positions': 10 ** 8}
    assert found['scopes']['moe/experts'] == pytest.approx(0.5)
    assert found['scopes']['moe/shared'] == pytest.approx(0.3)
    assert found['scopes']['mla/decode_read'] == pytest.approx(0.3)
    assert values['moe_experts_time_share'] == pytest.approx(25.0)
    assert values['mla_decode_read_time_share'] == pytest.approx(15.0)
    # bound by bytes: 1,240 experts' weights and 8,960 rows in and out
    nbytes = 1240 * 9437184 + 8960 * 2 * 2048 * 2
    assert 8960 * 6 * 2048 * 768 / 197e12 < nbytes / 819e9
    assert values['moe_experts_roofline'] == pytest.approx(
        100 * nbytes / 819e9 / 0.5)
    assert values['mla_decode_read_roofline'] == pytest.approx(
        100 * (1e8 * 1152 / 819e9) / 0.3)
    assert all(0 < v < 100 for v in values.values())


@pytest.mark.parametrize('run', [
    {}, {'registry': {}}, {'registry': {}, 'trace': None, 'counts': {}},
    {'registry': {'decode_expert_load_max_over_mean':
                  {'type': 'histogram', 'samples': []}}, 'counts': {}}])
def test_readers_find_nothing_in_a_run_without_the_records(run):
    run.setdefault('counts', {})
    for name in NEW:
        assert _reader(name).read(dict(run), Ctx()) is None, name


def test_trace_readers_find_nothing_where_the_program_names_no_scope(
        monkeypatch):
    """The parent's traced run: a device trace, no scopes, no work args."""
    from paddle_tpu import observability as obs
    with obs.telemetry_guard(True):
        run = _traced(monkeypatch, obs, [('jit(run)/dot_general', 1.5, 2.5)],
                      [('engine/step', 2.5, {})])
        try:
            for name in NEW[:4]:
                assert _reader(name).read(run, Ctx()) is None, name
        finally:
            obs.reset()


def test_registry_readers_on_a_run_written_by_hand():
    run = {'counts': {'pool_blocks': 35847}, 'registry': {
        'decode_expert_load_max_over_mean': {'type': 'histogram', 'samples': [
            {'labels': {'call': 'step'}, 'sum': 9.0, 'count': 4,
             'recent': []},
            {'labels': {'call': 'prefill'}, 'sum': 80.0, 'count': 2,
             'recent': []}]},
        'kv_cache_bytes_in_hbm': {'type': 'gauge', 'samples': [
            {'labels': {}, 'value': 6 * 35848 * 16 * 640 * 2}]}}}
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) == 2.25
    assert _reader('kv_cache_bytes_per_token').read(run, Ctx()) == 7680


@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_takes_family_runner_and_readers_through_the_harness(
        capsys, trace):
    harness = load('run.py', 'bench_run')
    rc = harness.main(['--workload', 'tiny_serve_routed', '--seed',
                       str(2 ** 31 + 11), '--seconds', '1', '--trace',
                       str(trace)], rehearsal=True, table=TABLE)
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and last['correct'] is True, out[-3000:]
    assert last['attempted'] > 0 and last['failed'] == 0
    with open(os.path.join(BENCH, 'out', 'tiny_serve_routed',
                           'last_run.json')) as f:
        run = json.load(f)['run']
    assert run['runner'] == 'serve_decode'
    errors = run['checks']['logit_err_prompt_len_prefill_decode']
    assert len(errors) == 3 and all(max(e[1:]) < 1e-4 for e in errors)
    if not trace:
        assert set(last['metrics']) == {'serve_tokens_per_s', 'setup_s'}
        return
    # off a TPU there is no device plane: the trace readers are left out,
    # the registry's are there (null off the chip: not counts)
    assert set(NEW[4:]) <= set(last['metrics'])
    assert not set(NEW[:4]) & set(last['metrics'])
    assert _reader('kv_cache_bytes_per_token').read(run, type(
        'C', (Ctx,), {'traffic': {'engine': {'block_size': 4}}})()) \
        == 3 * 128 * 4
    assert _reader('expert_load_max_over_mean').read(run, Ctx()) >= 1.0
    for name in ('decode_expert_assignments', 'decode_experts_touched',
                 'decode_context_positions_read'):
        assert run['registry'][name]['samples'][0]['value'] > 0
    assert run['compiles']['window']['compiles'] == 0


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, 'reference', 'kanana2_30b_a3b.py')) as f:
        source = f.read()
    assert 'paddle_tpu' not in source.split('"""', 2)[2]
    assert 'HIGHEST' in source and 'float32' in source
    assert os.path.exists(os.path.join(REPO, 'benchmark', 'programs',
                                       'kanana2_30b_a3b.py'))
