"""The traffic generator (benchmark/lib/loadgen.py): what it draws from the
seed, and the arithmetic that turns the clients' records into metrics."""
import json
import math

import numpy as np
import pytest

from bench_testlib import load

loadgen = load('lib/loadgen.py')

MIX = {'loop': 'closed', 'clients': 4, 'vocab': 50,
       'prompt_len': {'median': 12, 'sigma': 0.8, 'min': 4, 'max': 32},
       'output_len': {'median': 6, 'sigma': 0.8, 'min': 2, 'max': 16}}


def _round(seed, k, mix=MIX):
    """Round k as the closed loop's clients draw it: (prompt, max_new) per
    client, each from its own generator."""
    return [loadgen.draw_request(np.random.default_rng([seed, c]), mix, seed,
                                 k, c)
            for c in range(mix['clients'])]


def test_lengths_are_clipped_and_heavy_tailed():
    spec = MIX['prompt_len']
    lens = [loadgen.quantile_len(spec, (i + 0.5) / 2000) for i in range(2000)]
    assert min(lens) == 4 and max(lens) == 32
    assert lens == sorted(lens) and 11 <= np.median(lens) <= 13
    assert np.mean(lens) > np.median(lens)        # the tail is on the right
    assert loadgen.quantile_len(spec, 0.0) == 4
    assert loadgen.quantile_len(spec, 1.0) == 32


def test_the_seed_decides_lengths_places_and_tokens():
    mix = dict(MIX, clients=32)
    a, b = _round(7, 0, mix), _round(8, 0, mix)
    assert a == _round(7, 0, mix)
    # another seed: other tokens, and other lengths at the same places
    assert [p for p, _ in a] != [p for p, _ in b]
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert [n for _, n in a] != [n for _, n in b]
    # ... and so does another round of the same seed
    assert [len(p) for p, _ in _round(7, 1, mix)] != [len(p) for p, _ in a]


@pytest.mark.parametrize('seed,round_k', [(7, 0), (7, 1), (8, 0), (123, 5)])
def test_every_round_holds_every_quantile_once(seed, round_k):
    # whatever the seed and the round, the work of a round is the same:
    # that is what keeps tokens/s comparable from seed to seed
    mix = dict(MIX, clients=32)
    got = _round(seed, round_k, mix)
    for key, lens in (('prompt_len', [len(p) for p, _ in got]),
                      ('output_len', [n for _, n in got])):
        want = sorted(loadgen.quantile_len(mix[key], (i + 0.5) / 32)
                      for i in range(32))
        assert sorted(lens) == want
    # prompts and answers are dealt apart: a long prompt does not always
    # carry a long answer
    ranks = [loadgen.quantile_of(mix, seed, 'prompt', round_k, c)
             == loadgen.quantile_of(mix, seed, 'output', round_k, c)
             for c in range(32)]
    assert sum(ranks) < 8


def test_requests_stay_inside_the_mix():
    for k in range(20):
        for prompt, new in _round(1, k):
            assert 4 <= len(prompt) <= 32 and 2 <= new <= 16
            assert all(1 <= t < 50 for t in prompt)


def test_the_generator_plays_no_other_loop(tmp_path):
    spec = tmp_path / 'spec.json'
    spec.write_text(json.dumps({'load': dict(MIX, loop='open'), 'seed': 1,
                                'seconds': 1, 'port': 1, 'traced': False,
                                'request_timeout': 1,
                                'results': str(tmp_path / 'r.json')}))
    with pytest.raises(SystemExit) as refused:
        loadgen.main(str(spec))
    assert "only 'closed'" in str(refused.value)


def test_reduce_counts_what_the_clients_saw_in_the_window():
    results = {'open': 10.0, 'close': 20.0, 'records': [
        # sent before the window, tokens on both sides of its opening
        {'due': 8.0, 'sent': 8.0, 't': [9.0, 9.5, 10.5, 11.5], 'done': 11.5},
        # wholly inside: ttft 0.5 s from the DUE time, gaps 1 s and 2 s
        {'due': 12.0, 'sent': 12.1, 't': [12.5, 13.5, 15.5], 'done': 15.5},
        # failed inside: counts as +inf time to first token
        {'due': 13.0, 'sent': 13.0, 't': [], 'error': 'HTTP 503'},
        # sent inside, no token before the close: neither failed nor timed
        {'due': 19.9, 'sent': 19.9, 't': [], 'abandoned': True},
        # sent inside, streams across the close
        {'due': 18.0, 'sent': 18.0, 't': [19.0, 21.0], 'abandoned': True},
    ]}
    seen = loadgen.reduce(results)
    assert seen['window_s'] == 10.0
    assert seen['tokens'] == 2 + 3 + 1
    assert seen['attempted'] == 4 and seen['failed'] == 1
    assert seen['completed'] == 2 and seen['censored'] == 1
    assert sorted(seen['ttft_s'])[:2] == [0.5, 1.0]
    assert math.isinf(max(seen['ttft_s']))
    assert sorted(seen['itl_s']) == [1.0, 1.0, 2.0]
    assert len(seen['send_lag_s']) == 4
    assert abs(max(seen['send_lag_s']) - 0.1) < 1e-9
    assert seen['errors'] == ['HTTP 503']
