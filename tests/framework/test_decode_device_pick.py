"""The greedy pick is taken on the device (serving/decode/engine.py): an
engine call's one program returns the argmax of its logits rows beside them,
the host reads the ids (4 bytes a pick) and the rows cross only where the
call asks for them. Over the three cache kinds (`[k, v]` rows, latent rows,
a recurrent state) at the suite's tiny sizes: the ids are numpy's argmax of
the same call's rows (ties, NaN, infinities, idle slots); what a call copies
is what it asked for; one executable serves both kinds of call; and the
scheduler's token streams are those of the mechanism this replaced (every
row to the host, numpy's argmax there)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core.random import default_generator
from paddle_tpu.dygraph import guard
from paddle_tpu.dygraph.tape import Tensor
from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
from paddle_tpu.models.retention_lm import RetentionLM, RetentionLMConfig
from paddle_tpu.serving import DecodeEngine, DecodeScheduler, metrics
from paddle_tpu.serving.errors import UnsupportedCacheFeature

KINDS = {'kv': lambda: TransformerLM(CausalLMConfig.tiny()),
         'latent': lambda: LatentMoELM(LatentMoEConfig.tiny()),
         'state': lambda: RetentionLM(RetentionLMConfig.tiny())}
# what `_Doctored` makes of a row, by the token that selects it (id % 8)
CASES = ('plain', 'ties', 'one_nan', 'two_nans', 'all_nan', 'infinite',
         'all_minus_inf')
SLOTS = 4


class _Doctored:
    """``model`` with its logits rewritten inside the traced program, so
    that the rows an engine call returns hold ties, NaNs and infinities:
    the first token of each sequence of the call picks the case (a slot's
    fed token in a step, a prompt's first in a prefill). Everything else is
    the model's own."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, ids, pos_ids=None, cache=None):
        logits = self._model(ids, pos_ids=pos_ids, cache=cache).value
        case = ids.value[:, :1, None] % 8
        col = jnp.arange(logits.shape[-1])
        top = logits.max(-1, keepdims=True)
        made = {
            'ties': jnp.where((col == 7) | (col == 31), top + 1, logits),
            'one_nan': jnp.where(col == 9, jnp.nan, logits),
            'two_nans': jnp.where((col == 9) | (col == 20), jnp.nan, logits),
            'all_nan': jnp.full_like(logits, jnp.nan),
            'infinite': jnp.where((col == 5) | (col == 6), jnp.inf, logits),
            'all_minus_inf': jnp.full_like(logits, -jnp.inf)}
        for name, rows in made.items():
            logits = jnp.where(case == CASES.index(name), rows, logits)
        return Tensor(logits, stop_gradient=True)


def _engine(model, cls=DecodeEngine, **kw):
    kw.setdefault('slots', SLOTS)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 8)
    kw.setdefault('max_new_tokens_cap', 8)
    kw.setdefault('prompt_buckets', [8])
    kw.setdefault('prefix_cache', False)
    return cls(model, **kw)


@pytest.fixture(scope='module', params=sorted(KINDS))
def lm(request):
    with guard():
        default_generator.seed(5)
        model = KINDS[request.param]()
        model.eval()
        yield model


@pytest.fixture(scope='module')
def doctored_pair(lm):
    """Two engines of one geometry over the doctored model: they share the
    executables and each has a pool of its own, so that the same calls can
    be made once asking for the rows and once not."""
    model = _Doctored(lm)
    return _engine(model), _engine(model)


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


def _copied():
    return metrics.decode_logits_bytes_copied.value


def _spans(name):
    return [e for e in obs.tracer.snapshot()['traceEvents']
            if e.get('ph') == 'X' and e['name'] == name]


# -- (a) the ids are numpy's argmax of the same call's rows ------------------

@pytest.mark.parametrize('case', CASES)
def test_the_device_pick_is_numpys_argmax_of_the_calls_rows(
        doctored_pair, case):
    asks, greedy = doctored_pair
    code = CASES.index(case)
    prompt = [8 + code, 5, 6]
    grabbed = []

    def grab(row):
        grabbed.append(np.array(row))
        return 1

    t_asks, t_greedy = (e.reserve_table(3, 2) for e in (asks, greedy))
    assert asks.prefill(prompt, t_asks, sampler=grab) == 1
    row, = grabbed
    want = {'ties': 7, 'one_nan': 9, 'two_nans': 9, 'all_nan': 0,
            'infinite': 5, 'all_minus_inf': 0}
    if case in want:        # the row is the one the case was to make
        assert int(row.argmax()) == want[case]
    assert greedy.prefill(prompt, t_greedy) == int(row.argmax())
    # a step: slot 0 of this case, slot 1 of the next, two idle slots
    fed = [16 + code, 16 + (code + 1) % len(CASES), None, None]
    tables_asks = [t_asks, asks.reserve_table(1, 2), None, None]
    tables_greedy = [t_greedy, greedy.reserve_table(1, 2), None, None]
    ids, rows = asks.decode_step(fed, tables_asks, return_rows=True)
    alone = greedy.decode_step(fed, tables_greedy)
    for engine, tables in ((asks, tables_asks), (greedy, tables_greedy)):
        for table in tables[:2]:
            engine.release_table(table)
    assert ids.dtype == np.int32 and ids.shape == (SLOTS,)
    assert rows.shape[0] == SLOTS and rows.dtype == np.float32
    np.testing.assert_array_equal(ids, rows.argmax(-1))
    np.testing.assert_array_equal(alone, ids)
    if case in want:
        assert int(ids[0]) == want[case]


def test_the_verify_steps_program_picks_every_window_row(lm, monkeypatch):
    """The (S, K) program returns (S, K) picks of its (S, K, V) rows; the
    accept loop still reads the rows on the host, so they cross."""
    from paddle_tpu.serving.decode import engine as eng
    if lm.cache_layout().kind == 'state':
        with pytest.raises(UnsupportedCacheFeature, match='verify'):
            _engine(lm, spec_decode=True, spec_k=3)
        return
    fetched = []
    fetch = eng._CallClock.fetch
    monkeypatch.setattr(eng._CallClock, 'fetch', lambda self, *a: (
        fetched.append(fetch(self, *a)), fetched[-1])[1])
    engine = _engine(_Doctored(lm), spec_decode=True, spec_k=3)
    table = engine.reserve_table(3, 4)
    engine.prefill([8, 5, 6], table)
    before = _copied()
    rows = engine.spec_step([[17, 20, 21], None, None, None],
                            [table, None, None, None])
    picks, _, host_rows = fetched[-1]
    assert host_rows is rows and rows.shape[:2] == (SLOTS, 3)
    assert picks.shape == (SLOTS, 3) and picks.dtype == np.int32
    np.testing.assert_array_equal(picks, rows.argmax(-1))
    assert int(picks[0, 0]) == 7            # token 17: the tied case
    assert _copied() - before == picks.nbytes + rows.nbytes


# -- (b) a call copies what it asked for -------------------------------------

def test_a_greedy_call_copies_its_ids_and_a_rows_call_the_rows_too(lm):
    engine = _engine(lm)
    engine.warmup()
    obs.reset()
    with obs.telemetry_guard(True):
        tables = [engine.reserve_table(3, 4), engine.reserve_table(3, 4),
                  None, None]
        first = engine.prefill([3, 5, 7], tables[0])
        assert _copied() == 4
        grabbed = []
        second = engine.prefill(
            [4, 5, 7], tables[1],
            sampler=lambda row: grabbed.append(row) or int(row.argmax()))
        assert _copied() == 4 + 4 + grabbed[0].nbytes
        assert grabbed[0].nbytes == grabbed[0].shape[-1] * 4
        before = _copied()
        ids = engine.decode_step([first, second, None, None], tables)
        assert _copied() - before == SLOTS * 4
        before = _copied()
        _, rows = engine.decode_step([int(ids[0]), int(ids[1]), None, None],
                                     tables, return_rows=True)
        assert rows.shape == (SLOTS, grabbed[0].shape[-1])
        assert _copied() - before == SLOTS * 4 + rows.nbytes
    assert [e['args']['rows_fetched']
            for e in _spans('engine/prefill')] == [0, 1]
    assert [e['args']['rows_fetched'] for e in _spans('engine/step')] == [0, 1]


# -- (c) one executable serves both ------------------------------------------

def test_asking_for_rows_compiles_nothing(lm):
    engine = _engine(lm)
    engine.warmup()
    programs = engine.compiled_programs()
    tables = [engine.reserve_table(3, 4), engine.reserve_table(3, 4),
              None, None]
    fed = [engine.prefill([3, 5, 7], tables[0]),
           engine.prefill([4, 5, 7], tables[1],
                          sampler=lambda row: int(row.argmax())), None, None]
    for ask in (False, True, True, False):
        out = engine.decode_step(fed, tables, return_rows=ask)
        ids = out[0] if ask else out
        fed = [int(ids[0]), int(ids[1]), None, None]
    assert engine.compiled_programs() == programs


# -- (d) through the scheduler: the streams of the mechanism replaced --------

class _HostPick(DecodeEngine):
    """The mechanism the device pick replaced: every call hands its rows to
    the host, which takes numpy's argmax of them."""

    def prefill(self, prompt, table, sampler=None):
        return super().prefill(
            prompt, table, sampler=sampler or (lambda row: int(row.argmax())))

    def decode_step(self, tokens, tables, return_rows=False):
        _, rows = super().decode_step(tokens, tables, return_rows=True)
        ids = rows.argmax(-1)
        return (ids, rows) if return_rows else ids


PROMPTS = ([3, 9, 4], [7, 7, 2, 5, 1], [11], [2, 4, 6, 8, 10, 12, 14])
BUDGETS = (8, 5, 8, 6)


def _streams(engine, sampled):
    """The four requests admitted together (the worker starts once all are
    queued), the last one sampled where ``sampled``."""
    with DecodeScheduler(engine, start=False) as sched:
        streams = [sched.submit(
            p, max_new_tokens=n, request_id=f'pick-drill-{i}',
            sampling={'temperature': 0.9, 'top_k': 12}
            if sampled and i == len(PROMPTS) - 1 else None)
            for i, (p, n) in enumerate(zip(PROMPTS, BUDGETS))]
        sched._worker.start()
        return [s.result(120) for s in streams]


@pytest.mark.parametrize('sampled', [False, True],
                         ids=['greedy_batch', 'one_sampled_request'])
def test_the_schedulers_streams_are_those_of_the_host_pick(lm, sampled):
    want = _streams(_engine(lm, cls=_HostPick), sampled)
    obs.reset()
    got = _streams(_engine(lm), sampled)
    assert got == want
    assert [len(s) for s in got] == list(BUDGETS)
    steps = metrics.decode_steps.value
    ids_alone = 4 * len(PROMPTS) + 4 * SLOTS * steps
    if sampled:
        # its prefill's row, and all S rows of every step it emits in
        row = lm.cfg.vocab_size * 4
        assert _copied() == ids_alone + row + (BUDGETS[-1] - 1) * SLOTS * row
    else:
        assert _copied() == ids_alone
