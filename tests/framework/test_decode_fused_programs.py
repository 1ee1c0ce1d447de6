"""An engine call is ONE jitted XLA program (serving/decode/engine.py): the
programs are counted by XLA's own compile events and by the jitted
callable's cache, a warm call touches the eager per-op kernel cache not at
all, the pool is donated in and adopted back with no tracer left behind, and
the rows agree with the eager whole-sequence forward within a tolerance
(ROADMAP D1: a fused program and ~300 eager kernels round differently) while
the greedy token stream stays equal."""
import jax
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.dygraph import guard
from paddle_tpu.dygraph.tape import Tensor, no_grad_guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import DecodeEngine, DecodeScheduler
from paddle_tpu.serving.tier.replica import build_tiny_lm

BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'

# max |engine row - eager row| over max |eager row|, by the pool's dtype.
# f32: the same arithmetic fused differently: one ulp was seen (1.2e-7 at a
# logit scale of 0.47; worst 3.0e-7 over three weight draws), and 2e-6 is
# ~16 ulp, where a wrong mask, position or block moves a row by its own
# scale. bf16 and int8 add the rounding of every cached K/V row (2^-9 of a
# value; 1/254 of a row's largest), which this model's small attention
# terms shrink to 1.6e-5 and 5.2e-5 at worst; ten times that, and still
# under the next coarser dtype's error.
ROW_TOLERANCE = {'f32': 2e-6, 'bf16': 2e-4, 'int8': 6e-4}


@pytest.fixture(scope='module')
def lm():
    """Shared by the tests that count programs relative to what is there:
    the engine's programs are kept per model object."""
    with guard():
        yield build_tiny_lm()


@pytest.fixture()
def fresh_lm():
    """A model of its own: its program count starts at zero, and the test
    may change its weights."""
    with guard():
        yield build_tiny_lm()


@pytest.fixture(scope='module')
def xla_compiles():
    """Every executable XLA builds or loads in this process, appended as it
    happens (jax.monitoring has no public way to take a listener off)."""
    seen = []

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def make_engine(model, **kw):
    kw.setdefault('slots', 4)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 16)
    return DecodeEngine(model, **kw)


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(3, 100, n))) for n in lens]


@pytest.mark.parametrize('spec', [False, True])
def test_warm_engine_compiles_nothing_and_skips_the_kernel_cache(
        fresh_lm, xla_compiles, spec):
    eng = make_engine(fresh_lm, spec_decode=spec)
    assert eng.compiled_programs() == 0
    eng.warmup()
    programs = len(eng.prompt_buckets) + 1 + spec
    assert eng.compiled_programs() == programs
    profiler.reset_eager_kernel_cache_stats()
    compiles = len(xla_compiles)
    # every rung of the ladder, answers from 1 token to the cap
    lens = (1, 2, 3, 7, 12, 16, 5, 9)
    budgets = (1, 16, 4, 9, 2, 16, 7, 12)
    with DecodeScheduler(eng) as sched:
        streams = [sched.submit(p, max_new_tokens=m)
                   for p, m in zip(_prompts(0, lens), budgets)]
        outs = [s.result(120) for s in streams]
    assert [len(o) for o in outs] == list(budgets)
    stats = profiler.eager_kernel_cache_stats()
    assert (stats['hits'], stats['misses']) == (0, 0), stats
    assert len(xla_compiles) == compiles
    assert eng.compiled_programs() == programs


def test_engines_of_equal_geometry_share_the_models_programs(lm):
    first = make_engine(lm)
    first.warmup()
    programs = first.compiled_programs()
    second = make_engine(lm)
    second.warmup()
    assert second.compiled_programs() == programs
    make_engine(lm, slots=3).warmup()       # a new step shape, same prefills
    assert first.compiled_programs() == programs + 1


@pytest.mark.parametrize('kv_dtype', ['f32', 'bf16', 'int8'])
def test_rows_within_tolerance_of_the_eager_forward(lm, kv_dtype):
    eng = make_engine(lm, kv_dtype=kv_dtype)
    tol = ROW_TOLERANCE[kv_dtype]
    idle = [None] * (eng.slots - 1)
    for prompt in _prompts(1, (1, 3, 8, 13, 16)):
        P = len(prompt)
        got = []

        def grab(row):
            got.append(np.array(row))
            return int(row.argmax())

        table = eng.reserve_table(P, 4)
        toks = [eng.prefill(prompt, table, sampler=grab)]
        for _ in range(3):
            ids, rows = eng.decode_step([toks[-1]] + idle, [table] + idle,
                                        return_rows=True)
            assert rows.shape == (eng.slots, lm.cfg.vocab_size)
            assert ids[0] == rows[0].argmax()
            got.append(np.array(rows[0]))
            toks.append(int(ids[0]))
        eng.release_table(table)
        buf = np.zeros((1, eng.padded_context), np.int64)
        buf[0, :P + 3] = prompt + toks[:3]
        with no_grad_guard():
            want = np.asarray(
                lm(Tensor(buf, stop_gradient=True)).numpy())[0, P - 1:P + 3]
        assert got[0].shape == (lm.cfg.vocab_size,)
        err = np.abs(np.stack(got) - want).max(-1) / np.abs(want).max()
        assert err.max() <= tol, (kv_dtype, P, err)


def test_token_stream_equals_greedy_generate(lm):
    eng = make_engine(lm)
    prompts = _prompts(2, (3, 7, 12, 5, 9, 1, 16))
    budgets = [10, 4, 16, 7, 12, 16, 2]
    with DecodeScheduler(eng) as sched:
        streams = [sched.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        outs = [s.result(120) for s in streams]
    assert outs == [greedy_generate(lm, p, m, pad_len=eng.padded_context)
                    for p, m in zip(prompts, budgets)]


@pytest.mark.parametrize('kv_dtype', ['f32', 'int8'])
def test_pool_is_donated_and_adopted_with_no_tracer_left(lm, kv_dtype):
    eng = make_engine(lm, kv_dtype=kv_dtype)
    table = eng.reserve_table(5, 4)
    tok = eng.prefill([3, 5, 7, 9, 11], table)        # allocates the pool
    idle = [None] * (eng.slots - 1)

    def held():
        layers, scales = eng.pool.arrays()
        return [a for kv in list(layers.values()) + list(scales.values())
                for a in kv]

    assert len(held()) == lm.num_cache_layers * (4 if kv_dtype == 'int8'
                                                 else 2)
    resident = eng.pool.bytes_in_hbm()
    calls = [lambda: eng.prefill([4, 6, 8], eng.reserve_table(3, 2)),
             lambda: eng.decode_step([tok] + idle, [table] + idle)]
    for call in calls:
        before = held()
        call()
        assert all(a.is_deleted() for a in before)
        after = held()
        assert not any(isinstance(a, jax.core.Tracer) for a in after)
        assert not any(a.is_deleted() for a in after)
        assert eng.pool.bytes_in_hbm() == resident


def test_sampler_and_rows_run_the_greedy_calls_programs(lm, xla_compiles):
    eng = make_engine(lm)
    idle = [None] * (eng.slots - 1)
    table = eng.reserve_table(3, 4)
    tok = eng.prefill([3, 5, 7], table)
    eng.decode_step([tok] + idle, [table] + idle)
    programs, compiles = eng.compiled_programs(), len(xla_compiles)
    table2 = eng.reserve_table(3, 4)
    tok2 = eng.prefill([3, 5, 7], table2,
                       sampler=lambda row: int(row.argmax()))
    ids, rows = eng.decode_step([tok2] + idle, [table2] + idle,
                                return_rows=True)
    assert tok2 == tok and rows.shape == (eng.slots, lm.cfg.vocab_size)
    assert eng.compiled_programs() == programs
    assert len(xla_compiles) == compiles


def test_a_swapped_weight_is_served_without_a_compile(fresh_lm,
                                                      xla_compiles):
    """Parameters are arguments of the programs, not constants in them."""
    lm = fresh_lm
    eng = make_engine(lm)
    prompt = [5, 9, 2, 44]
    with DecodeScheduler(eng) as sched:
        old = sched.submit(prompt, max_new_tokens=8).result(120)
    assert old == greedy_generate(lm, prompt, 8, pad_len=eng.padded_context)
    programs, compiles = eng.compiled_programs(), len(xla_compiles)
    pos_emb = lm.pos_emb.weight
    pos_emb.value = pos_emb.value[::-1] * 3.0
    want = greedy_generate(lm, prompt, 8, pad_len=eng.padded_context)
    compiles = len(xla_compiles)        # past the reference's own kernels
    with DecodeScheduler(eng) as sched:
        new = sched.submit(prompt, max_new_tokens=8).result(120)
    assert new == want and new != old
    assert eng.compiled_programs() == programs
    assert len(xla_compiles) == compiles
