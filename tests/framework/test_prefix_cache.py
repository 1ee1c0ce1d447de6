"""Radix prefix cache (paddle_tpu/serving/tier/prefix_cache.py): bitwise
hit-vs-cold parity, shared-prefix refcount lifecycle, LRU eviction under
pool pressure, block-boundary rules, and the always-on prefix_cache_*
metrics."""
import numpy as np
import pytest

from paddle_tpu.dygraph import guard
from paddle_tpu.models.causal_lm import greedy_generate
from paddle_tpu.serving import DecodeEngine, DecodeScheduler, PrefixCache
from paddle_tpu.serving.tier.replica import build_tiny_lm
from shared_programs import build_shared_prompt_work


@pytest.fixture(scope='module')
def lm():
    with guard():
        yield build_tiny_lm()


def make_engine(model, **kw):
    kw.setdefault('slots', 2)
    kw.setdefault('block_size', 4)
    kw.setdefault('max_blocks', 64)
    kw.setdefault('max_prompt_len', 16)
    kw.setdefault('max_new_tokens_cap', 8)
    kw.setdefault('prefix_cache', True)
    return DecodeEngine(model, **kw)


def _counter(name):
    from paddle_tpu.observability import registry
    d = registry.to_dict().get(name)
    if not d or not d['samples']:
        return 0.0
    return sum(s['value'] for s in d['samples'])


SYS_PROMPT = [7, 3, 11, 5, 9, 2, 44, 8]          # two whole 4-token blocks


# -- strict knob parse -----------------------------------------------------

def test_prefix_cache_env_strict_parse(lm, monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE', 'yes')
    with pytest.raises(ValueError, match="'0', '1'"):
        make_engine(lm, prefix_cache=None)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE', '1')
    eng = make_engine(lm, prefix_cache=None)
    assert eng.prefix_cache is not None
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE', '0')
    assert make_engine(lm, prefix_cache=None).prefix_cache is None


def test_prefix_cache_max_blocks_env_strict_parse(lm, monkeypatch):
    eng = make_engine(lm, prefix_cache=False)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS', 'many')
    with pytest.raises(ValueError, match='PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS'):
        PrefixCache(eng.pool)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS', '-3')
    with pytest.raises(ValueError, match='integers >= 0'):
        PrefixCache(eng.pool)
    monkeypatch.setenv('PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS', '7')
    assert PrefixCache(eng.pool).max_blocks == 7


# -- bitwise parity (the load-bearing contract) ----------------------------

def test_hit_bitwise_equals_cold_and_reference(lm):
    """Cold miss, then the identical prompt again as a cache hit: both
    generations must be array_equal to the uncached whole-sequence greedy
    reference — and to each other."""
    eng = make_engine(lm)
    prompt = SYS_PROMPT + [13, 21]
    ref = greedy_generate(lm, prompt, 6, pad_len=eng.padded_context)
    h0, s0 = _counter('prefix_cache_hits'), _counter('prefix_cache_tokens_saved')
    with DecodeScheduler(eng) as sched:
        cold = sched.submit(prompt, max_new_tokens=6).result(120)
        hit = sched.submit(prompt, max_new_tokens=6).result(120)
    assert cold == ref
    assert hit == ref
    assert _counter('prefix_cache_hits') - h0 == 1
    assert _counter('prefix_cache_tokens_saved') - s0 == 8  # 2 blocks * 4


def test_shared_system_prompt_different_suffixes(lm):
    """The tier's motivating workload: one shared system prompt, per-user
    suffixes. Every suffixed request after the first hits the shared
    blocks and still produces its OWN reference bytes."""
    eng = make_engine(lm)
    suffixes = ([13, 21], [17, 6], [99, 1, 2], [40])
    prompts = [SYS_PROMPT + s for s in suffixes]
    refs = [greedy_generate(lm, p, 5, pad_len=eng.padded_context)
            for p in prompts]
    h0 = _counter('prefix_cache_hits')
    with DecodeScheduler(eng) as sched:
        outs = [sched.submit(p, max_new_tokens=5).result(120)
                for p in prompts]
    assert outs == refs
    assert _counter('prefix_cache_hits') - h0 == len(prompts) - 1


@pytest.mark.parametrize('enabled', [False, True], ids=['off', 'on'])
def test_shared_prompt_mix_with_the_cache_on_and_off(lm, enabled):
    """One 12-token system prompt under twelve short user suffixes,
    submitted together: bitwise the uncached reference either way; with
    the cache on the always-on metrics show a hit rate AND prefill tokens
    saved above zero, with it off no lookup at all."""
    work = build_shared_prompt_work(12)
    eng = make_engine(lm, slots=4, max_blocks=256, prefix_cache=enabled)
    refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
            for p, m in work]
    names = ('prefix_cache_hits', 'prefix_cache_misses',
             'prefix_cache_tokens_saved')
    before = [_counter(n) for n in names]
    with DecodeScheduler(eng, queue_depth=len(work) + 1) as sched:
        streams = [sched.submit(p, max_new_tokens=m) for p, m in work]
        outs = [s.result(300) for s in streams]
    hits, misses, saved = (_counter(n) - b for n, b in zip(names, before))
    assert outs == refs
    if enabled:
        assert hits > 0 and hits + misses == len(work)
        assert saved > 0
    else:
        assert hits == misses == saved == 0


def test_concurrent_mixed_workload_parity(lm):
    """Ragged concurrent mix of cold and hitting prompts through the
    continuous-batching scheduler stays bitwise."""
    eng = make_engine(lm, slots=3)
    rng = np.random.RandomState(3)
    prompts = [SYS_PROMPT + list(map(int, rng.randint(3, 100, n)))
               for n in (1, 3, 2, 5, 1, 4)]
    budgets = [6, 3, 8, 2, 5, 7]
    refs = [greedy_generate(lm, p, m, pad_len=eng.padded_context)
            for p, m in zip(prompts, budgets)]
    with DecodeScheduler(eng) as sched:
        streams = [sched.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        outs = [s.result(120) for s in streams]
    assert outs == refs


# -- refcount lifecycle ----------------------------------------------------

def test_shared_prefix_refcount_lifecycle(lm):
    """cache-resident +1, one per sharing table: 2 while one request holds
    it, 3 while two share, back to 1 (cache only) after both retire, 0
    (freed) after eviction."""
    eng = make_engine(lm)
    alloc = eng.pool.allocator
    prompt = SYS_PROMPT + [13]
    t1 = eng.reserve_table(len(prompt), 4, prompt=prompt)
    assert t1.cached_len == 0                    # cold
    eng.prefill(prompt, t1)
    eng.publish_prefix(prompt, t1)
    shared_ids = eng.prefix_cache.resident_block_ids()
    assert len(shared_ids) == 2
    assert all(alloc.refcount(b) == 2 for b in shared_ids)   # t1 + cache
    t2 = eng.reserve_table(len(prompt), 4, prompt=prompt)
    assert t2.cached_len == 8
    assert t2.blocks[:2] == t1.blocks[:2]        # zero-copy sharing
    assert all(alloc.refcount(b) == 3 for b in shared_ids)
    eng.release_table(t1)
    assert all(alloc.refcount(b) == 2 for b in shared_ids)
    eng.release_table(t2)
    assert all(alloc.refcount(b) == 1 for b in shared_ids)   # cache only
    used_before = alloc.used
    assert eng.prefix_cache.evict_idle() == 2
    assert alloc.used == used_before - 2
    assert all(alloc.refcount(b) == 0 for b in shared_ids)


def test_sharing_request_never_writes_shared_blocks(lm):
    """A hitting request's writes all land in its fresh blocks: the shared
    prefix blocks' bytes are identical before and after the hit
    generation."""
    eng = make_engine(lm)
    prompt = SYS_PROMPT + [13, 21]
    with DecodeScheduler(eng) as sched:
        sched.submit(prompt, max_new_tokens=6).result(120)
        ids = eng.prefix_cache.resident_block_ids()
        before = [eng.pool.read_blocks(layer, ids)
                  for layer in range(eng.pool.num_layers)]
        sched.submit(prompt, max_new_tokens=6).result(120)
        after = [eng.pool.read_blocks(layer, ids)
                 for layer in range(eng.pool.num_layers)]
    for (kb, vb), (ka, va) in zip(before, after):
        assert np.array_equal(kb, ka) and np.array_equal(vb, va)


# -- eviction --------------------------------------------------------------

def test_eviction_under_pool_pressure(lm):
    """A pool too small to hold the cache AND a new request evicts idle
    cached blocks (LRU) instead of failing or waiting forever — and the
    evicted-and-recomputed generation is still bitwise."""
    # capacity 5; each request needs ceil((8+8)/4) = 4 blocks
    eng = make_engine(lm, max_blocks=6, max_prompt_len=8,
                      max_new_tokens_cap=8)
    p1 = SYS_PROMPT
    p2 = [91, 92, 93, 94, 95, 96, 97, 98]
    r1 = greedy_generate(lm, p1, 8, pad_len=eng.padded_context)
    r2 = greedy_generate(lm, p2, 8, pad_len=eng.padded_context)
    e0 = _counter('prefix_cache_evicted_blocks')
    with DecodeScheduler(eng) as sched:
        assert sched.submit(p1, max_new_tokens=8).result(120) == r1
        # p1's 2 cached blocks + 4 fresh would exceed capacity: evict
        assert sched.submit(p2, max_new_tokens=8).result(120) == r2
        # and p1 again — its cache entries were (partly) evicted, still exact
        assert sched.submit(p1, max_new_tokens=8).result(120) == r1
    assert _counter('prefix_cache_evicted_blocks') - e0 >= 1
    assert eng.pool.allocator.used == eng.prefix_cache.resident_blocks


def test_max_blocks_cap_bounds_residency(lm):
    eng = make_engine(lm, prefix_cache=False)
    eng.prefix_cache = PrefixCache(eng.pool, max_blocks=1)
    prompt = SYS_PROMPT                       # would publish 2 blocks
    table = eng.reserve_table(len(prompt), 4, prompt=prompt)
    eng.prefill(prompt, table)
    eng.publish_prefix(prompt, table)
    assert eng.prefix_cache.resident_blocks <= 1
    eng.release_table(table)
    eng.prefix_cache.evict_idle()


def test_lru_prefers_older_idle_leaves(lm):
    """Under pressure the LRU victim is the least-recently-matched leaf."""
    eng = make_engine(lm)
    pc = eng.prefix_cache
    pa = SYS_PROMPT + [13]                    # publishes 2 blocks
    pb = [91, 92, 93, 94, 95]                 # publishes 1 block, later
    for p in (pa, pb):
        t = eng.reserve_table(len(p), 4, prompt=p)
        eng.prefill(p, t)
        eng.publish_prefix(p, t)
        eng.release_table(t)
    # touch pa: the match re-stamps its whole path newer than pb's insert
    # (the retain is released right away — this is a recency touch only)
    blocks = pc.match(pa)
    assert len(blocks) == 2
    eng.pool.allocator.release(blocks)
    assert pc._evict_one()
    assert tuple(pb[:4]) not in pc._root.children    # older leaf evicted
    assert tuple(pa[:4]) in pc._root.children        # touched path survives


# -- block-boundary rules --------------------------------------------------

def test_sub_block_prompts_never_cached(lm):
    eng = make_engine(lm)
    with DecodeScheduler(eng) as sched:
        sched.submit([1, 2, 3], max_new_tokens=3).result(120)   # < 1 block
        assert eng.prefix_cache.resident_blocks == 0
        m0 = _counter('prefix_cache_misses')
        sched.submit([1, 2, 3], max_new_tokens=3).result(120)
        assert _counter('prefix_cache_misses') - m0 == 1        # still cold


def test_last_prompt_token_never_served_from_cache(lm):
    """A block-aligned prompt (P == k * block_size) may hit at most k-1
    blocks: at least one real token must run through the model to produce
    the first generated token's logits."""
    eng = make_engine(lm)
    prompt = SYS_PROMPT                       # exactly 2 blocks
    ref = greedy_generate(lm, prompt, 4, pad_len=eng.padded_context)
    with DecodeScheduler(eng) as sched:
        assert sched.submit(prompt, max_new_tokens=4).result(120) == ref
        t = eng.reserve_table(len(prompt), 4, prompt=prompt)
        assert t.cached_len == 4              # 1 block, not 2
        eng.release_table(t)
        assert sched.submit(prompt, max_new_tokens=4).result(120) == ref


def test_trie_deepens_with_longer_shared_prompts(lm):
    """A longer prompt sharing a cached prefix publishes the DEEPER blocks;
    later prompts hit the extended path."""
    eng = make_engine(lm)
    pa = SYS_PROMPT                               # blocks 0,1
    pb = SYS_PROMPT + [61, 62, 63, 64]            # + block 2
    pc_prompt = pb + [33]
    refs = [greedy_generate(lm, p, 4, pad_len=eng.padded_context)
            for p in (pa, pb, pc_prompt)]
    with DecodeScheduler(eng) as sched:
        assert sched.submit(pa, max_new_tokens=4).result(120) == refs[0]
        assert sched.submit(pb, max_new_tokens=4).result(120) == refs[1]
        assert eng.prefix_cache.resident_blocks == 3
        s0 = _counter('prefix_cache_tokens_saved')
        assert sched.submit(pc_prompt, max_new_tokens=4).result(120) == refs[2]
        assert _counter('prefix_cache_tokens_saved') - s0 == 12   # 3 blocks


def test_metrics_exported(lm):
    from paddle_tpu.observability import registry
    eng = make_engine(lm)
    with DecodeScheduler(eng) as sched:
        sched.submit(SYS_PROMPT, max_new_tokens=2).result(120)
        sched.submit(SYS_PROMPT, max_new_tokens=2).result(120)
    d = registry.to_dict()
    for name in ('prefix_cache_hits', 'prefix_cache_misses',
                 'prefix_cache_tokens_saved', 'prefix_cache_blocks_resident',
                 'prefix_cache_inserted_blocks',
                 'prefix_cache_evicted_blocks'):
        assert name in d, f'missing prefix-cache metric {name}'
