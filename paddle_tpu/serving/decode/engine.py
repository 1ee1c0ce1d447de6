"""DecodeEngine: phase-split stateful generation over a paged KV cache.

Every engine call runs the model as ONE jitted XLA program (`_Program`):
parameters, buffers and the pool's per-layer arrays are its arguments (K/V
or latent rows per token; for a retention layer one recurrent state per
request, kv_cache.py "Recurrent state"; all DONATED, so the paged writes and
the state updates happen in place), everything about
the request is a small traced array (token ids, positions, write
coordinates, block tables, context lengths), and it returns the logits rows,
their greedy pick (the rows' argmax, taken on the device) and the new pool
arrays. The host's part of a call is packing those arrays, one dispatch, one
wait and one copy of the ids: 4 bytes a slot. The rows cross only where the
call asks for them (a request with a sampler, the speculative accept loop, a
check that reads logits), and the host then runs the request's sampler.

The compile-count story (the whole point — models/transformer.py's original
decode re-compiled per generated length):

- **prefill** runs the prompt once at a bucket-ladder shape (reusing
  serving.engine.bucket_ladder — powers of two up to ``max_prompt_len``),
  writing its K/V into cache blocks, and hands the host the pick of the
  prompt's last row, sliced on the device (and the row, to a sampler): one
  program per rung, ``len(prompt_buckets)`` compiles, ever.
- **decode** steps all S slots in lockstep at ONE fixed shape
  ((S, 1) tokens + (S, max_blocks_per_seq) tables + (S,) context lengths):
  exactly one program, regardless of how long any sequence runs (one more,
  at (S, k), with speculation on).

The programs are kept per MODEL object and keyed by the pool's geometry, so
engines of equal geometry over one model share executables.
tests/framework/test_decode_fused_programs.py asserts the counts by XLA's
own compile events, and that a warm engine call touches the eager per-op
kernel cache not at all.

Numerical contract with the uncached whole-sequence forward
(models/causal_lm.py, ROADMAP D1): equal greedy token streams, and logits
rows within a stated tolerance — a fused program and ~300 eager kernels
round differently (one ulp seen on the CPU: 1.2e-7 at a logit scale of
0.47). Exact equality holds between runs of the SAME program only: replay
by request id, spill and reinject, a same-dtype handoff. The lockstep
step's read walks the batch's live blocks (ops/nn_ops.py:paged_attention);
prefill rungs below 128 and the (S, K) step still read ``padded_context``
positions a slot.

The engine is single-threaded by design (one scheduler worker owns it);
it holds no queueing or lifecycle logic — that is scheduler.py.
"""
from __future__ import annotations

import contextlib
import functools
import re
import time
import weakref

import jax
import numpy as np

from .. import metrics as _m
from ... import observability as _obs
from ...observability import distributed as _dobs
from ...core.compile_cache import setup_persistent_cache
from ...dygraph.jit import _bind
from ...dygraph.tape import Tensor, no_grad_guard
from ...ops.llm_ops import diffusion_pick
from ...ops.nn_ops import (group_walk_pads, live_blocks_taken,
                           live_groups_taken, live_ring_groups_taken)
from ..engine import bucket_ladder
from ..errors import InvalidRequest
from .diffusion import unmask_most_confident
from .kv_cache import (BlockTable, CacheContext, KVCachePool, decode_coords,
                       prefill_coords, DEFAULT_BLOCK_SIZE, DEFAULT_MAX_BLOCKS,
                       DEFAULT_SLOTS, KV_DTYPE_CODES)
from .layout import SLIDING_SPARE_BLOCKS, solve_decode_pool_blocks

__all__ = ['DecodeEngine', 'SLIDING_SPARE_BLOCKS']

_NULL_LOCK = contextlib.nullcontext()

# an HLO instruction that moves data and nothing else: `%name = <result
# shapes> copy(...)`, transpose, or an async copy's start; and the dims of
# each array shape in the result
_HLO_MOVE = re.compile(r'=\s*(.*?)\s(?:copy|copy-start|transpose)\(')
_HLO_DIMS = re.compile(r'\w+\[([\d,]+)\]')


def _moves_of_size(hlo_text, sizes):
    """The instructions of a compiled program's text that copy or transpose
    an array of one of the element counts ``sizes``."""
    moves = []
    for line in hlo_text.splitlines():
        made = _HLO_MOVE.search(line)
        if made and any(
                int(np.prod([int(d) for d in dims.split(',')])) in sizes
                for dims in _HLO_DIMS.findall(made.group(1))):
            moves.append(line.strip())
    return moves


def _arrays_spanning(hlo_text, positions):
    """The instructions of a compiled program's text with an array in which
    a run of adjacent dimensions multiplies to ``positions``: for S slots ×
    their padded context, the dense per-slot copies and score arrays of a
    read that pads every slot to the table's width, whatever their trailing
    dimensions ((S·blocks, block, W), (S, T, H, D), (S, T, H), ...)."""
    found = []
    for line in hlo_text.splitlines():
        for dims in _HLO_DIMS.findall(line):
            dims = [int(d) for d in dims.split(',')]
            if any(int(np.prod(dims[i:j])) == positions
                   for i in range(len(dims))
                   for j in range(i + 1, len(dims) + 1)):
                found.append(line.strip())
                break
    return found


class _Program:
    """``model`` as the pure function every engine call runs, jitted:

        run(mode, geometry, params, buffers, layers, scales,
            ids, pos, coords, last) -> (rows, picks, stats, layers, scales)

    ``mode`` ('prefill' | 'decode') and the pool's ``geometry`` are static;
    ``layers`` / ``scales`` (the pool's arrays, a state layer's among
    ``layers``) are donated and come back written; parameters are arguments, read from the model at each call, so
    no weight is baked into an executable and a swapped weight is served.
    The trace binds them the way dygraph/jit.py::functionalize does, and
    the pool it writes is a `KVCachePool.over` the traced arrays: nothing
    traced outlives the trace. ``picks`` is what the host needs and no more:
    the int32 argmax of ``rows`` over the vocabulary, of the very numbers
    ``rows`` returns (the first of equal maxima; a row with a NaN gives its
    first NaN's index: numpy's argmax on the same rows). ``rows``, with
    ``picks`` in brackets: prefill (1, L) -> row ``last`` (V,) [()]; decode
    (S, 1) -> (S, V) [(S,)]; decode (S, K) -> (S, K, V) [(S, K)]. A WINDOW
    model (its layout's ``window`` B > 1, block diffusion) steps (S, B) ->
    (S, B, V) [a pair: (S, B) int32 ids with the `MASK` column left out,
    and (S, B) float32 softmax probabilities of those ids,
    ops/llm_ops.py::diffusion_pick], and its prefill returns neither rows
    nor picks: it writes the prompt's K/V and scores nothing. The rows
    stay an output of the one program, the head's matmul result, for the
    calls that ask for them: an output nobody reads is never copied.
    ``stats`` is what the forward noted on its
    `CacheContext` for the host, stacked per name and otherwise untouched
    (a model with routed experts: the rows each expert was given and the
    experts of the scored rows; else empty).

    One per model object (:meth:`of`), shared by every engine over it: jit's
    own cache then holds one executable per (mode, geometry, shapes)."""

    _by_model = weakref.WeakKeyDictionary()

    @classmethod
    def of(cls, model):
        prog = cls._by_model.get(model)
        if prog is None:
            prog = cls._by_model[model] = cls(model)
        return prog

    def __init__(self, model):
        setup_persistent_cache()
        self._params = dict(model.named_parameters())
        self._buffers = dict(model.named_buffers())
        # the jitted closure must not keep the model alive: it is the value
        # of a dictionary that holds the model weakly
        model_ref = weakref.ref(model)
        params, buffers = self._params, self._buffers
        # layer -> (heads, head_dim) of a K/V row, as every trace's pool
        # noted them (the model's own, so the same each time): what
        # `KVCachePool.allocate` is told beside the arrays' shapes, which do
        # not say it
        heads = self._heads = {}
        # a window model (models/block_diffusion_lm.py) feeds a step
        # ``window`` rows a slot and hands the host, per row, a pick and the
        # confidence in it; its prefill scores nothing
        window = model.cache_layout().window
        pick = {'mask_token_id': int(getattr(model, 'mask_token_id', -1))}

        def run(mode, geometry, pvals, bvals, layers, scales, ids, pos,
                coords, last):
            pool = KVCachePool.over(geometry, layers, scales)
            ctx = CacheContext(pool, mode, coords, last)
            if pos is not None:
                pos = Tensor(pos, stop_gradient=True)
            with _bind(params, pvals), _bind(buffers, bvals), \
                    no_grad_guard():
                logits = model_ref()(Tensor(ids, stop_gradient=True),
                                     pos_ids=pos, cache=ctx)
                logits = None if logits is None else logits.value
            if logits is None:
                rows = None         # a window model's prefill: K/V alone
            elif mode == 'prefill':
                # a model may have scored row `last` alone: (1, 1, V)
                rows = jax.lax.dynamic_index_in_dim(
                    logits[0], jax.numpy.minimum(last, logits.shape[1] - 1),
                    0, keepdims=False)
            elif ids.shape[1] == 1:
                rows = logits[:, 0]
            else:
                rows = logits
            # what the forward noted for the host (`CacheContext.note`)
            stats = {name: jax.numpy.stack(values)
                     for name, values in ctx.stats.items()}
            heads.update(pool.heads)
            if rows is None:
                picks = None
            elif window > 1:
                # (ids, confidences), each (S, B): the scope names the
                # pick's device ops in a profiler trace
                with jax.named_scope('diffusion/pick'):
                    picks = diffusion_pick(rows, **pick)
            else:
                picks = jax.numpy.argmax(rows, -1).astype(jax.numpy.int32)
            return (rows, picks, stats) + pool.arrays()

        self.jitted = jax.jit(run, static_argnums=(0, 1),
                              donate_argnums=(4, 5))

    def _head(self, pool, mode):
        """The program's arguments before the pool's arrays."""
        return (mode, pool.geometry,
                {n: p.value for n, p in self._params.items()},
                {n: b.value for n, b in self._buffers.items()})

    def __call__(self, pool, mode, ids, pos, coords, last=None):
        """Run one engine call's program over ``pool`` and return its rows,
        their picks and the forward's ``stats`` (for a model with routed
        experts ``expert_counts`` (layers, E) and ``expert_ids`` (layers,
        scored rows, k), else empty), all device arrays: the call is
        enqueued, not finished."""
        fn = functools.partial(self.jitted, *self._head(pool, mode))
        if not pool.num_layers:
            # the pool allocates here, before the first trace that takes its
            # arrays as arguments: an abstract trace over an empty pool
            # returns the arrays `ensure_layer` would make
            pool.allocate(*jax.eval_shape(fn, {}, {}, ids, pos, coords,
                                          last)[3:], self._heads)
        rows, picks, stats, layers, scales = fn(*pool.arrays(), ids, pos,
                                                coords, last)
        pool.adopt(layers, scales)
        return rows, picks, stats

    def lower(self, pool, mode, ids, pos, coords, last=None, sharding=None):
        """The program `__call__` would run over ``pool`` (allocated),
        lowered and not run: nothing is donated. With ``sharding`` (of a
        device that may be described and not attached) every argument is
        its shape and dtype there, so `.compile()` is that device's."""
        head = self._head(pool, mode)
        args = head[2:] + pool.arrays() + (ids, pos, coords, last)
        if sharding is not None:
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=sharding), args)
        return self.jitted.lower(*head[:2], *args)


class _CallClock:
    """perf_counter stamps at the phase boundaries of one engine call
    (``call``: prefill | step | spec_step). Each phase runs from the stamp
    before it to its own, so the phases tile the call: pack (the host
    arrays the program reads), forward (the dispatch of the call's ONE
    program: arguments handed over, the program enqueued; its trace and
    compile too, the first time a shape is seen), device_wait
    (``block_until_ready`` on the picks: host idle, the device running the
    program — the device's time for the call), logits_copy (what crosses,
    device to host: the picks, the few kB of counts the counters read, and
    the rows where the call asked for them), sample (what the host still
    does for the pick: an ``int()``, or the request's sampler on its row).

    Beside every perf_counter stamp stands one of the thread-CPU clock
    (``time.thread_time``, which counts the calling thread only while it
    runs): a phase's wall less its CPU is the time the thread was off the
    CPU in it, waiting for the interpreter lock, for the device or in a
    blocking call. In ``pack`` and ``sample``, which never block of
    themselves, that is the wait for the interpreter lock.

    ``record`` is the one place the stamps are read: always one
    observation per phase into ``decode_engine_phase_seconds`` and its CPU
    seconds into ``decode_engine_phase_cpu_seconds``, and with telemetry on
    the ``engine/<call>`` span and its ``engine/<call>/<phase>`` children
    from the wall stamps (the CPU seconds are read as sums over a window,
    never call by call: where the host's thread clock advances in ticks of
    10 ms one call's reading is 0 or a tick). The span's args carry the call's
    ``work`` (expert assignments, experts touched, context positions and
    cache blocks read: what the counters were given for this call;
    ``rows_fetched`` 1 where the rows crossed to the host, 0 where the picks
    alone did), so that a trace reader can set the device time of a slice
    against the work of the calls in it. O(1) per call.

    The engine keeps its newest clock as ``DecodeEngine.last_call``: the
    scheduler reads where the call's phases began and ended from it, and
    books what lies between them and its own as ``book``."""

    __slots__ = ('call', 'start', 'start_cpu', 'last', 'last_cpu', 'ends',
                 'cpu_ends', 'work')

    def __init__(self, call):
        self.call = call
        self.ends = []                  # [(phase, perf_counter at its end)]
        self.cpu_ends = []              # [thread_time at its end], beside
        self.work = {}                  # args of the call's span: its work
        self.start = self.last = time.perf_counter()
        self.start_cpu = self.last_cpu = time.thread_time()

    def end(self, phase):
        self.last = time.perf_counter()
        self.last_cpu = time.thread_time()
        self.ends.append((phase, self.last))
        self.cpu_ends.append(self.last_cpu)
        return self.last

    def fetch(self, picks, counts=None, rows=None):
        """The picks on the host, with the wait for the device and the copy
        stamped apart (``device_get`` alone is both at once); ``counts`` (a
        small array of the same program) and ``rows`` (given only by a call
        that needs them on the host) are copied in the same phase.
        ``picks`` may be a tuple of arrays (a window model's ids and
        confidences) or None (its prefill: the caller has waited for the
        program by the pool's arrays).
        ``decode_logits_bytes_copied`` takes what crossed for the pick:
        4 bytes a pick (and 4 a confidence), and the rows' bytes where they
        were asked for."""
        jax.block_until_ready(picks)
        self.end('device_wait')
        # one round of transfers, started together (None stays None)
        picks, counts, rows = jax.device_get((picks, counts, rows))
        self.end('logits_copy')
        _m.decode_logits_bytes_copied.inc(
            sum(x.nbytes for x in jax.tree_util.tree_leaves(picks))
            + (0 if rows is None else rows.nbytes))
        self.work['rows_fetched'] = int(rows is not None)
        return picks, counts, rows

    def record(self, **args):
        hist = _m.decode_engine_phase_seconds
        cpu_s = _m.decode_engine_phase_cpu_seconds
        spans = _obs._ENABLED
        name = 'engine/' + self.call
        t, c = self.start, self.start_cpu
        for (phase, end), cpu in zip(self.ends, self.cpu_ends):
            hist.labels(call=self.call, phase=phase).observe(end - t)
            cpu_s.labels(call=self.call, phase=phase).inc(cpu - c)
            if spans:
                _obs.tracer.complete(f'{name}/{phase}', t, end)
            t, c = end, cpu
        if spans:
            _obs.tracer.complete(name, self.start, self.last, **self.work,
                                 **args)


class DecodeEngine:
    """Stateful generation over ``model`` (anything with the
    models/causal_lm.py forward contract: ``model(ids, pos_ids=None,
    cache=None) -> logits``, its layers routing ``cache=`` into the
    `CacheContext`, and ``model.cache_layout()``: what each of them caches,
    layout.py).

    - ``slots``: fixed lockstep decode batch size S.
    - ``block_size`` / ``max_blocks``: KV-cache pool geometry.
    - ``max_prompt_len``: top rung of the prefill bucket ladder.
    - ``max_new_tokens_cap``: per-request generation budget cap (block
      reservations are taken against prompt + budget at admission, so the
      cap bounds what one request can strand).
    - ``spec_decode`` / ``spec_k``: speculative decoding (the batched
      (S, k) verify step — :meth:`spec_step`). Arg wins, else the
      ``PADDLE_TPU_SPEC_DECODE`` knob, default OFF; an explicit env ``0``
      is the hard escape hatch and wins even over ``spec_decode=True``
      (an operator must be able to disable speculation on a deployed
      binary without a code change).
    """

    def __init__(self, model, slots=None, block_size=None, max_blocks=None,
                 max_prompt_len=64, max_new_tokens_cap=64,
                 prompt_buckets=None, eos_id=None, prefix_cache=None,
                 model_lock=None, spec_decode=None, spec_k=None,
                 kv_dtype=None):
        self.model = model
        if hasattr(model, 'eval'):
            model.eval()           # generation is inference: no dropout
        # colocated disaggregation (serving/tier/disagg.py) runs a prefill
        # engine's forwards on a worker thread beside this engine's decode
        # steps; a shared lock serializes the two MODEL calls (the dygraph
        # tape's no_grad flag is process-global). None = zero overhead.
        self._model_lock = model_lock
        self._program = _Program.of(model)
        # what each layer caches, how a step reads it, what it costs and
        # what it refuses (layout.py): the pool, the counters and the
        # refusals below all ask it
        self.layout = layout = model.cache_layout()
        # rows a slot feeds the lockstep step: 1 for every model but a
        # WINDOW model (block diffusion), whose step is `window_step`
        self.window = layout.window
        # the last call's ``stats`` as its program returned them (device
        # arrays, read by whoever asks: nothing is copied on the served
        # path): ``expert_ids`` says which experts made the rows just read
        self.last_stats = {}
        # the newest call's clock (`_CallClock`): where its phases began and
        # ended, wall and thread-CPU, for the scheduler's bookkeeping leaf
        self.last_call = None
        self.slots = int(slots or DEFAULT_SLOTS)
        self.max_prompt_len = int(max_prompt_len)
        self.max_new_tokens_cap = int(max_new_tokens_cap)
        self.eos_id = eos_id
        self.prompt_buckets = bucket_ladder(self.max_prompt_len,
                                            prompt_buckets)
        block_size = int(block_size or DEFAULT_BLOCK_SIZE)
        if block_size % self.window:
            raise ValueError(
                f'block_size={block_size} is no multiple of the model\'s '
                f'window of {self.window}: a block of the model must never '
                f'straddle two cache blocks')
        max_total = self.max_prompt_len + self.max_new_tokens_cap
        max_bps = -(-max_total // block_size)
        # KV storage dtype: arg wins, else the strict-parsed
        # PADDLE_TPU_KV_DTYPE knob (default f32 — the unquantized path)
        from ..tier.knobs import (ENV_KV_DTYPE, KV_DTYPE_CHOICES,
                                  parse_choice_env)
        if kv_dtype is None:
            kv_dtype = parse_choice_env(ENV_KV_DTYPE, KV_DTYPE_CHOICES,
                                        'f32')
        num_blocks = self._resolve_num_blocks(model, max_blocks, block_size,
                                              max_bps, kv_dtype, self.slots)
        # state layers: a row a slot, and the scratch row of idle slots;
        # the sliding class: a ring a slot and the spare
        self.pool = KVCachePool(
            block_size=block_size, num_blocks=num_blocks,
            max_blocks_per_seq=max_bps, kv_dtype=kv_dtype,
            state_rows=layout.state_rows(self.slots), span=layout.span,
            sliding_blocks=layout.sliding_blocks(self.slots, block_size),
            layout=layout)
        # whether the grouped reads take whole chunks of groups (the XLA
        # walk) or the live groups alone (the pallas kernel on a TPU): what
        # a step's count of the blocks it read goes by
        self._group_pads = layout.group_reads \
            and group_walk_pads(self.pool.dtype)
        if self.pool.allocator.capacity < max_bps:
            # an empty pool must always cover one maximal request, or the
            # scheduler's FIFO head could wait forever
            raise ValueError(
                f'max_blocks={self.pool.num_blocks} cannot hold one '
                f'maximal request ({max_bps} blocks for '
                f'{max_total} tokens at block_size={block_size})')
        _m.decode_slots_total.set(self.slots)
        _m.decode_cache_blocks_total.set(self.pool.allocator.capacity)
        _m.kv_cache_dtype.set(KV_DTYPE_CODES[self.pool.kv_dtype])
        self._set_state_gauges()
        self._prefill_compiled = set()
        self._step_compiled = False
        self._spec_compiled = False
        # speculative decoding: env '0' is the hard escape hatch (wins over
        # the arg); otherwise arg wins, else env, default off
        from ..tier.knobs import (ENV_SPEC_DECODE, ENV_SPEC_K,
                                  parse_flag_env, parse_int_env)
        import os as _os
        env_raw = _os.environ.get(ENV_SPEC_DECODE, '').strip()
        if env_raw == '0':
            self.spec_enabled = False
        elif spec_decode is not None:
            self.spec_enabled = bool(spec_decode)
        else:
            self.spec_enabled = parse_flag_env(ENV_SPEC_DECODE,
                                               default=False)
        self.spec_k = int(spec_k if spec_k is not None
                          else parse_int_env(ENV_SPEC_K, 4, minimum=2))
        if self.spec_k < 2:
            raise ValueError(f'spec_k must be >= 2, got {self.spec_k}')
        # radix prefix cache (serving/tier/prefix_cache.py): arg wins, else
        # the strict-parsed PADDLE_TPU_PREFIX_CACHE env knob (default off)
        from ..tier.knobs import ENV_PREFIX_CACHE, parse_flag_env
        if prefix_cache is None:
            prefix_cache = parse_flag_env(ENV_PREFIX_CACHE, default=False)
        if prefix_cache is False:
            self.prefix_cache = None
        elif prefix_cache is True:
            from ..tier.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(self.pool)
        else:
            self.prefix_cache = prefix_cache
        # what the layout cannot serve is refused here, never under traffic
        # (layout.py, the one table); the handoff where its prefill role is
        # built (serving/tier/disagg.py)
        layout.refuse(prefix_cache=self.prefix_cache is not None,
                      spec_decode=self.spec_enabled, kv_dtype=kv_dtype)

    @staticmethod
    def _resolve_num_blocks(model, max_blocks, block_size, max_bps,
                            kv_dtype, slots):
        """Pool-size precedence (docs/SERVING.md "Tiered KV cache"): an
        explicit ``max_blocks=`` arg wins, then an explicitly-SET
        ``PADDLE_TPU_DECODE_MAX_BLOCKS`` env (checked live, not the
        import-time default — an operator pinning the block count must
        beat any budget), then the ``PADDLE_TPU_DECODE_HBM_MB`` budget
        solve (layout.py prices model state + per-block KV bytes at
        ``kv_dtype``), else the module default."""
        if max_blocks:
            return int(max_blocks)
        import os as _os
        raw = _os.environ.get('PADDLE_TPU_DECODE_MAX_BLOCKS', '').strip()
        if raw:
            return int(raw)
        from ..tier.knobs import ENV_DECODE_HBM_MB, parse_int_env
        hbm_mb = parse_int_env(ENV_DECODE_HBM_MB, 0, minimum=1)
        if hbm_mb:
            return solve_decode_pool_blocks(
                model, hbm_mb, block_size=block_size, kv_dtype=kv_dtype,
                min_blocks=max_bps + 1, slots=slots)
        return DEFAULT_MAX_BLOCKS

    # -- geometry ----------------------------------------------------------
    @property
    def block_size(self):
        return self.pool.block_size

    @property
    def padded_context(self):
        """The positions a slot's block table spans, the key extent of the
        reads that still gather it whole — run the uncached reference
        (models/causal_lm.greedy_generate) at this pad_len for identical
        tokens."""
        return self.pool.padded_context

    def validate(self, prompt_ids, max_new_tokens):
        """Typed admission checks; returns (prompt list, max_new int)."""
        try:
            prompt = [int(t) for t in prompt_ids]
        except (TypeError, ValueError) as e:
            raise InvalidRequest(f'prompt must be a sequence of ints: {e}')
        if not prompt:
            raise InvalidRequest('empty prompt')
        if len(prompt) > self.max_prompt_len:
            raise InvalidRequest(
                f'prompt of {len(prompt)} tokens exceeds max_prompt_len='
                f'{self.max_prompt_len}')
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise InvalidRequest(f'max_new_tokens must be >= 1, got '
                                 f'{max_new}')
        if max_new > self.max_new_tokens_cap:
            raise InvalidRequest(
                f'max_new_tokens={max_new} exceeds the engine cap '
                f'{self.max_new_tokens_cap}')
        return prompt, max_new

    def reserve_table(self, prompt_len, max_new_tokens, prompt=None):
        """Block reservation for prompt + budget (raises OutOfBlocks — the
        scheduler treats that as 'wait for a finishing slot'). With the
        prefix cache enabled and ``prompt`` given, the table's front blocks
        are shared cached-prefix blocks (``table.cached_len`` > 0) and only
        the remainder is freshly allocated."""
        total = int(prompt_len) + int(max_new_tokens)
        if self.prefix_cache is not None:
            return self.prefix_cache.acquire_table(prompt or [], total)
        return self.pool.new_table(total)

    def publish_prefix(self, prompt, table):
        """Publish ``table``'s whole-prompt blocks into the prefix cache
        (no-op when the cache is off). The scheduler calls this once the
        full prompt's K/V is cached — after a cold prefill, a suffix fill,
        or a disaggregated injection."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(prompt, table)

    def release_table(self, table):
        self.pool.free_table(table)
        self._set_block_gauges()
        self._set_state_gauges()

    def _set_block_gauges(self):
        """Blocks held by live requests: the full class's (the one gauge a
        pool of one class has), and where the model has layer classes each
        class's own."""
        used = self.pool.allocator.used
        _m.decode_cache_blocks_used.set(used)
        if self.layout.classes:
            _m.decode_full_blocks_held.set(used)
            _m.decode_sliding_blocks_held.set(
                self.pool.sliding.used if self.layout.span else 0)

    def _set_state_gauges(self):
        """The state layers' three gauges (a telemetry reset clears gauges,
        so every call that changes one sets all three); nothing for a model
        of row layers alone."""
        if self.layout.state_layers:
            _m.state_cache_bytes_in_hbm.set(self.pool.state_bytes_in_hbm())
            _m.state_cache_rows_total.set(self.pool.state_rows.capacity)
            _m.state_cache_rows_used.set(self.pool.state_rows.used)

    # -- phases ------------------------------------------------------------
    def _run(self, clock, mode, ids, pos, coords, last=None,
             fetch_rows=False):
        """The call's one program, from dispatch to its picks on the host
        (and, with ``fetch_rows``, the rows they are the argmax of; else
        None), stamping forward, device_wait and logits_copy on ``clock``.
        The model lock is held throughout: a first call of a shape traces
        the model with its parameters bound to tracers, which a second
        engine over the same model (serving/tier/disagg.py) must not see."""
        with self._model_lock or _NULL_LOCK:
            rows, picks, stats = self._program(self.pool, mode, ids, pos,
                                               coords, last)
            clock.end('forward')
            if picks is None:
                # a window model's prefill picks nothing: the program is
                # done when the pool it wrote is
                jax.block_until_ready(self.pool.arrays())
            counts = stats.get('expert_counts')
            if 'expert_assignments' in stats:
                # a share of the experts: all the assignments beside it
                counts = (counts, stats['expert_assignments'])
            picks, counts, rows = clock.fetch(picks, counts,
                                              rows if fetch_rows else None)
        self.last_stats = stats
        if counts is not None:
            clock.work.update(self._account_experts(clock.call, counts))
        return picks, rows

    @staticmethod
    def _account_experts(call, counts):
        """One engine call's routing, from the (layers, E) rows each expert
        was given of the call's live tokens (a rung's padding and idle
        slots are routed and computed too, and not counted: the counters
        hold the work the mathematics needs): assignments, experts that
        got at least one row, and the worst layer's largest load over its
        mean. Where the layers hold a SHARE of their experts ``counts`` is
        the pair (the held experts' rows, every layer's assignments held
        here or elsewhere): the old counters and args keep the work done
        here, and two more say of how much it is the share."""
        total = None
        if isinstance(counts, tuple):
            counts, total = counts
        work = {'expert_assignments': int(counts.sum()),
                'experts_touched': int((counts > 0).sum())}
        if total is not None:
            work['assignments_held'] = work['expert_assignments']
            work['assignments_total'] = int(total.sum())
            _m.decode_expert_assignments_held.inc(work['assignments_held'])
            _m.decode_expert_assignments_total.inc(work['assignments_total'])
        _m.decode_expert_assignments.inc(work['expert_assignments'])
        _m.decode_experts_touched.inc(work['experts_touched'])
        _m.decode_expert_load_max_over_mean.labels(call=call).observe(
            float((counts.max(1) / np.maximum(counts.mean(1), 1e-9)).max()))
        return work

    def compiled_programs(self):
        """Executables held for this engine's MODEL, over every engine and
        geometry that ran it: after warm-up, for one engine on a fresh
        model, ``len(prompt_buckets) + 1`` (+ 1 with speculation on)."""
        return self._program.jitted._cache_size()

    def lowered(self, bucket=None, sharding=None):
        """The lockstep step's program, or with ``bucket`` that prefill
        rung's, lowered over this engine's pool (allocated: after a first
        call or warm-up) and not run; ``sharding`` as `_Program.lower`
        takes it. What the programs hold is read from here: `pool_moves`,
        chip_smoke.py, the tests."""
        pool = self.pool
        if bucket is None:
            feed = np.zeros((self.slots, self.window), np.int64)
            return self._program.lower(
                pool, 'decode', feed, feed,
                decode_coords(pool, [None] * self.slots, [1] * self.slots,
                              window=self.window, block=self.window > 1),
                sharding=sharding)
        return self._program.lower(
            pool, 'prefill', np.zeros((1, bucket), np.int64), None,
            prefill_coords(pool, BlockTable([], pool.block_size), bucket),
            np.int32(0), sharding=sharding)

    def pool_moves(self, bucket=None, sharding=None):
        """The instructions of that program, compiled for the device it
        would run on (or ``sharding``'s), that copy or transpose an array
        of the size of one of the pool's: a layout the compiler chose for
        the pool against the writes' and the read's. Must be empty
        (kv_cache.py's module docstring): the pool's arrays lie as the
        paged scatter and the page gather take them, and the scatter is in
        place."""
        layers, scales = self.pool.arrays()
        sizes = {int(a.size) for arrs in list(layers.values())
                 + list(scales.values()) for a in arrs}
        return _moves_of_size(self._compiled_text(bucket, sharding), sizes)

    def _compiled_text(self, bucket=None, sharding=None):
        return self.lowered(bucket, sharding).compile().as_text()

    def step_context_arrays(self, sharding=None):
        """The instructions of the lockstep step, compiled as `pool_moves`
        compiles it, that hold an array over every slot's whole padded
        context (S × ``padded_context`` positions). Must be empty for a K/V
        pool and for a latent one: the step's read walks the live blocks,
        or the live groups of blocks, a chunk at a time
        (ops/nn_ops.py::paged_attention, ops/llm_ops.py::
        mla_decode_attention) and builds no per-slot dense copy. It says so
        only where the tables hold more than one chunk of blocks (of
        groups): a shorter table is one chunk, whole."""
        return _arrays_spanning(self._compiled_text(None, sharding),
                                self.slots * self.padded_context)

    def prefill(self, prompt, table, sampler=None):
        """Run the bucket-padded prompt once, writing K/V into ``table``'s
        blocks, and return the FIRST generated token — greedy (the row's
        argmax, taken on the device: 4 bytes reach the host), or drawn by
        ``sampler(logits_row)`` for sampled requests (the row is copied to
        the host for it). Sets ``table.context_len = len(prompt)``."""
        if self.window > 1:
            return self._prefill_window(prompt, table, sampler)
        clock = self.last_call = _CallClock('prefill')
        P = len(prompt)
        bucket = next(b for b in self.prompt_buckets if P <= b)
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :P] = prompt
        table.context_len = P
        coords = prefill_coords(self.pool, table, bucket)
        t0 = clock.end('pack')
        pick, row = self._run(clock, 'prefill', ids, None, coords,
                              np.int32(P - 1),
                              fetch_rows=sampler is not None)
        _m.decode_prefill_seconds.observe(clock.last - t0)
        token = int(pick if sampler is None else sampler(row))
        clock.end('sample')
        folded = P * self.layout.state_layers
        if folded:
            _m.decode_state_tokens_folded.inc(folded)
            clock.work['state_tokens_folded'] = folded
        self._account_conv(clock, P, rung=bucket)
        if self.layout.classes:
            # positions the prompt leaves in each class's blocks
            clock.work.update(self._class_positions([P])[1])
        clock.record(prompt_len=P, bucket=bucket)
        self._after_prefill(bucket)
        return token

    def _after_prefill(self, bucket):
        if bucket not in self._prefill_compiled:
            self._prefill_compiled.add(bucket)
            _m.decode_prefill_compiles.inc()
        self._set_block_gauges()
        _m.kv_cache_bytes_in_hbm.set(self.pool.bytes_in_hbm())
        _m.kv_cache_row_bytes.set(self.pool.row_bytes())
        self._set_state_gauges()

    def _prefill_window(self, prompt, table, sampler=None):
        """A window model's prefill: the prompt's ⌊P/B⌋ WHOLE blocks run
        once at the rung that holds them, under the block mask, and their
        K/V are written; ``table.context_len`` = ⌊P/B⌋·B. Nothing is scored
        or picked and None is returned: the prompt's last P mod B tokens
        open the first block beside its masks, and the first denoising
        forward (:meth:`window_step`) reads them. A prompt shorter than a
        block runs no program at all."""
        if sampler is not None:
            raise InvalidRequest(
                'a window model picks by confidence and takes no sampler')
        clock = self.last_call = _CallClock('prefill')
        P = len(prompt)
        n = P // self.window * self.window
        table.context_len = n
        if not n:
            return None
        bucket = next(b for b in self.prompt_buckets if n <= b)
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :n] = prompt[:n]
        coords = prefill_coords(self.pool, table, bucket)
        t0 = clock.end('pack')
        self._run(clock, 'prefill', ids, None, coords, np.int32(n - 1))
        _m.decode_prefill_seconds.observe(clock.last - t0)
        clock.end('sample')
        clock.record(prompt_len=P, bucket=bucket)
        self._after_prefill(bucket)
        return None

    def decode_step(self, tokens, tables, return_rows=False):
        """One lockstep step over all S slots at fixed shape.

        ``tokens``: length-S list, the token to feed per slot (None =
        inactive). ``tables``: length-S list of BlockTables (None =
        inactive). For an active slot with context c, the fed token is the
        one at position c (it was sampled from the previous step/prefill
        but not yet cached); its K/V are written and attended this step.
        Returns (S,) next-token ids (greedy: the rows' argmax, taken on the
        device, so S × 4 bytes reach the host; garbage on inactive slots)
        and advances each active table's context_len by 1. With
        ``return_rows=True`` the raw (S, V) logits rows are copied to the
        host too (``(ids, rows)``) so the scheduler can sample non-greedy
        slots — the same executable runs and the ids are the argmax of
        those same rows, so requesting rows changes no bits."""
        clock = self.last_call = _CallClock('step')
        S = self.slots
        assert len(tokens) == S and len(tables) == S
        ids = np.zeros((S, 1), np.int64)
        pos = np.zeros((S, 1), np.int64)
        ctx_lens = []
        for s in range(S):
            if tables[s] is None:
                ctx_lens.append(1)          # scratch read, masked + ignored
                continue
            c = tables[s].context_len
            ids[s, 0] = tokens[s]
            pos[s, 0] = c
            tables[s].context_len = c + 1   # the fed token becomes cached
            ctx_lens.append(c + 1)
        coords = decode_coords(self.pool, tables, ctx_lens)
        blocks = self._blocks_walked(ctx_lens)
        t0 = clock.end('pack')
        out, rows = self._run(clock, 'decode', ids, pos, coords,
                              fetch_rows=return_rows)
        dt = clock.end('sample') - t0
        self._account_step(clock, dt, tables, blocks)
        self._step_compiled = True
        if return_rows:
            return out, rows
        return out

    def _blocks_walked(self, ctx_lens):
        """Blocks the row layers' reads take from the pool in a lockstep
        step over ``ctx_lens`` (an idle slot's 1: the scratch block), summed
        over the layers, each read as ops/nn_ops.py walks it (the layout's
        ``read``); a state layer reads no block."""
        pool = self.pool
        bs, mb = pool.block_size, pool.max_blocks_per_seq
        walked = 0
        for read, layers in self.layout.reads:
            if read == 'blocks':
                walked += layers * live_blocks_taken(ctx_lens, bs, mb)
            elif read == 'ring':
                walked += layers * live_ring_groups_taken(
                    ctx_lens, bs, pool.ring, pool.span, self._group_pads)
            else:       # a latent row, a window's block, a full class
                walked += layers * live_groups_taken(ctx_lens, bs, mb,
                                                     self._group_pads)
        return walked

    def _account_step(self, clock, dt, tables, blocks, attended=None):
        """What every decode step books, lockstep or speculative, and the
        call's record; ``blocks`` the cache blocks the layers' reads took,
        ``attended`` the positions a layer's read attended where that is
        not the tables' contexts (a window step reads a block it may not
        keep)."""
        _m.decode_step_seconds.observe(dt)
        _m.decode_steps.inc()
        active = sum(t is not None for t in tables)
        # the live context the step attended, the fed tokens included, and
        # the blocks its reads took from the pool to attend it: both over
        # the layers that cache rows (a state layer attends no position and
        # reads no block: it advances one state a live slot)
        if self.layout.classes:
            # two classes: a sliding layer attends its span's positions
            positions, by_class = self._class_positions(
                [t.context_len for t in tables if t is not None])
            clock.work.update(by_class)
        else:
            positions = self.layout.row_layers * (
                attended if attended is not None else sum(
                    t.context_len for t in tables if t is not None))
        _m.decode_context_positions_read.inc(positions)
        _m.decode_kv_blocks_read.inc(blocks)
        clock.work['context_positions'] = positions
        clock.work['kv_blocks'] = blocks
        updates = self.layout.state_layers * active
        if updates:
            _m.decode_state_updates.inc(updates)
            clock.work['state_updates'] = updates
        self._account_conv(clock, active)
        clock.record()
        _m.decode_slots_active.set(active)
        _m.decode_slot_occupancy.observe(active / max(self.slots, 1))
        # sliding-window views for /healthz slo + fleet snapshots
        _dobs.series('occupancy').observe(active / max(self.slots, 1))
        _dobs.series('decode_step').observe(dt)

    def _account_conv(self, clock, rows, **args):
        """A call's live rows through the gated short convolutions, ``rows``
        x conv layers (a prefill's prompt, never its rung; a step's live
        slots): the counter, and the span's ``conv_rows`` with ``args``
        beside it. Nothing for a model without such a layer."""
        if self.layout.conv_layers:
            conv = rows * self.layout.conv_layers
            _m.decode_conv_rows.inc(conv)
            clock.work.update(args, conv_rows=conv)

    def _class_positions(self, contexts):
        """(positions the layers hold of ``contexts``, the call's span args
        by class): Σ over the contexts of ``context`` a full layer and
        ``min(context, span)`` a sliding layer. Books
        ``decode_kv_positions_held`` with it and
        ``decode_kv_positions_if_unwindowed`` with what every layer would
        hold were none sliding: the ring's saving, read off two counters."""
        layout = self.layout
        n_full, n_sliding = layout.full_layers, layout.sliding_layers
        total = sum(int(c) for c in contexts)
        within = sum(min(int(c), layout.span) for c in contexts)
        full, sliding = n_full * total, n_sliding * within
        _m.decode_kv_positions_held.inc(full + sliding)
        _m.decode_kv_positions_if_unwindowed.inc(
            (n_full + n_sliding) * total)
        return full + sliding, {'full_positions': full,
                                'sliding_positions': sliding}

    def window_step(self, blocks, masked, quota, tables, commits,
                    return_rows=False):
        """One lockstep step of a WINDOW model (block diffusion): every live
        slot feeds its whole block of B = ``window`` tokens over its cache.
        It IS the engine's step: the same clock, histograms and span as
        :meth:`decode_step`, one program, one shape.

        ``blocks`` (S, B) int and ``masked`` (S, B) bool are the caller's
        host arrays of every slot's block: its token ids, the model's `MASK`
        id where a position is still masked, and which positions those are.
        ``tables``: length-S list, None = inactive. ``commits``: length-S
        flags. For a live slot with context c the block is fed at positions
        c .. c+B-1, every row attending positions < c+B (the block mask);
        its K/V are written there, PROVISIONALLY: ``context_len`` moves to
        c+B where ``commits[s]`` says so (a commit forward: the block is
        finished, its K/V are kept) and not at all otherwise (a denoising
        forward: the next forward of the block writes the same positions
        again, and no later read sees them before that: rollback is one
        integer never stored).

        The host is handed (S, B) ids and (S, B) float32 confidences
        (ops/llm_ops.py::diffusion_pick), S × B × 8 bytes, never a logits
        row; in the call's ``sample`` phase the schedule
        (diffusion.py::unmask_most_confident) fixes, IN PLACE in ``blocks``
        and ``masked``, the ``quota[s]`` most confident masked positions of
        each denoising slot to their picks. Returns ``(ids, confidences)``
        (garbage on inactive slots; of a commit forward nobody's concern),
        and with ``return_rows=True`` the (S, B, V) rows too, for a check."""
        clock = self.last_call = _CallClock('step')
        S, B = self.slots, self.window
        assert B > 1 and len(tables) == S and len(commits) == S
        live = np.asarray([t is not None for t in tables])
        commits = live & np.asarray(commits, bool)
        contexts = np.asarray([t.context_len if t is not None else 0
                               for t in tables], np.int64)
        ids = np.where(live[:, None], blocks, 0)
        pos = np.where(live[:, None], contexts[:, None] + np.arange(B), 0)
        # an idle slot reads the scratch block, masked and ignored
        extents = np.where(live, contexts + B, 1)
        coords = decode_coords(self.pool, tables, extents, live * B, B,
                               block=True)
        walked = self._blocks_walked(extents)
        t0 = clock.end('pack')
        (picks, conf), rows = self._run(clock, 'decode', ids, pos, coords,
                                        fetch_rows=return_rows)
        for s in np.flatnonzero(commits):
            tables[s].context_len += B      # the block's K/V are kept
        unmasked = unmask_most_confident(
            blocks, masked, picks, conf,
            np.where(live & ~commits, np.asarray(quota), 0))
        dt = clock.end('sample') - t0
        forwards, committed = int(live.sum()), int(commits.sum())
        _m.decode_diffusion_denoise_forwards.inc(forwards - committed)
        _m.decode_diffusion_commit_forwards.inc(committed)
        clock.work.update(window=B, slot_forwards=forwards,
                          commits=committed, rows_unmasked=unmasked)
        self._account_step(clock, dt, tables, walked,
                           attended=int(extents[live].sum()))
        self._step_compiled = True
        if return_rows:
            return picks, conf, rows
        return picks, conf

    def spec_step(self, token_lists, tables):
        """One batched (S, k) speculative/multi-token step.

        ``token_lists``: length-S list; None or [] for an inactive slot,
        else UP TO ``spec_k`` tokens to feed — the slot's pending token
        first, then its draft guesses (or further prompt tokens during a
        chunked suffix fill). All fed tokens' K/V are written at positions
        context_len .. context_len+f-1 and each table's ``context_len``
        advances by f; the CALLER rolls rejected tails back by assigning
        ``table.context_len = base + accepted`` (block ids don't move —
        rollback is one integer store, and the overwritten tail positions
        are masked until rewritten, per the kv_cache scratch contract).

        Returns (S, k, V) logits rows: row j of a slot is the target
        model's distribution AFTER fed tokens 0..j — the (S, 1) lockstep
        row at the same context, up to the rounding of another program (the
        multi-query `paged_attention` staircase;
        tests/framework/test_spec_decode.py asserts equal token streams
        across ragged accept lengths). Padded lanes (j >= f)
        are garbage on scratch reads and must be ignored."""
        clock = self.last_call = _CallClock('spec_step')
        S, K = self.slots, self.spec_k
        assert len(token_lists) == S and len(tables) == S
        ids = np.zeros((S, K), np.int64)
        pos = np.zeros((S, K), np.int64)
        ctx_lens, fed_counts = [], []
        for s in range(S):
            toks = token_lists[s]
            if tables[s] is None or not toks:
                ctx_lens.append(1)      # scratch read, masked + ignored
                fed_counts.append(0)
                continue
            f = min(len(toks), K)
            c = tables[s].context_len
            ids[s, :f] = toks[:f]
            pos[s, :f] = np.arange(c, c + f)
            pos[s, f:] = c + max(f - 1, 0)   # padded lanes: in-range dummy
            tables[s].context_len = c + f
            ctx_lens.append(c + 1)
            fed_counts.append(f)
        coords = decode_coords(self.pool, tables, ctx_lens,
                               fed_counts=fed_counts, window=K)
        t0 = clock.end('pack')
        # the accept loop reads the rows on the host (scheduler._spec_step)
        _, rows = self._run(clock, 'decode', ids, pos, coords,
                            fetch_rows=True)
        dt = clock.last - t0
        self._spec_compiled = True
        # it IS the decode step; its (S, K) read gathers every table whole
        self._account_step(clock, dt, tables, self.layout.row_layers * S
                           * self.pool.max_blocks_per_seq)
        _m.decode_spec_verify_seconds.observe(dt)
        _m.decode_spec_rounds.inc()
        return rows

    def inject_prefill(self, table, payload):
        """Receive a disaggregated prefill (serving/tier/disagg.py): write
        the payload's whole K/V blocks into ``table``'s first blocks of
        THIS pool and mark the prompt cached. Returns the payload's first
        greedy token. ``table.cached_len`` blocks at the front (shared
        prefix-cache blocks) are already filled and are skipped."""
        bs = self.pool.block_size
        if payload.block_size != bs:
            raise InvalidRequest(
                f'handoff block_size {payload.block_size} != engine '
                f'block_size {bs}')
        skip = table.cached_len // bs          # shared blocks already filled
        nb = payload.num_blocks
        if nb > len(table.blocks):
            raise InvalidRequest(
                f'handoff carries {nb} blocks but the table reserves only '
                f'{len(table.blocks)}')
        for layer, (k, v) in enumerate(payload.layers):
            ks = vs = None
            if payload.scales is not None:
                ks, vs = payload.scales[layer]
            if skip:
                k, v = k[:, skip:], v[:, skip:]
                if ks is not None:
                    ks, vs = ks[:, skip:], vs[:, skip:]
            if k.shape[1]:
                self.pool.write_whole_blocks(
                    layer, table.blocks[skip:nb], k, v,
                    k_scale=ks, v_scale=vs)
        table.context_len = payload.context_len
        _m.decode_cache_blocks_used.set(self.pool.allocator.used)
        _m.kv_cache_bytes_in_hbm.set(self.pool.bytes_in_hbm())
        return int(payload.first_token)

    # -- warmup ------------------------------------------------------------
    @property
    def warmed(self):
        """True once the whole prefill bucket ladder AND the lockstep
        decode-step shape have compiled (via :meth:`warmup` or organic
        traffic). Surfaced through ``/healthz`` so the serving-tier router
        never sends traffic into a cold replica's compile cliff."""
        return (self._step_compiled
                and (self._spec_compiled or not self.spec_enabled)
                and all(b in self._prefill_compiled
                        for b in self._rungs_run()))

    def _rungs_run(self):
        """The ladder's rungs a prefill can run at: all of them, but for a
        window model those that hold a whole block (a shorter prompt runs
        no prefill program: its tokens open the first block)."""
        return [b for b in self.prompt_buckets if b >= self.window]

    def warmup(self):
        """Precompile the prefill ladder + the decode-step shape (+ the
        (S, k) speculative verify shape when enabled) before traffic
        arrives (same contract as InferenceEngine.warmup). Returns
        {phase: seconds}. Uses temporary blocks; the pool ends unchanged."""
        timings = {}
        # a sampled request draws each token with a device op
        # (sampling.TokenSampler): one throwaway draw compiles it here, not
        # under the first sampled request
        from .sampling import SamplingParams, TokenSampler
        t0 = time.perf_counter()
        TokenSampler(SamplingParams(temperature=1.0), 'warmup').sample(
            np.zeros(2, np.float32), 0)
        timings['sampler'] = time.perf_counter() - t0
        for bucket in self._rungs_run():
            # reserve spec_k headroom so the warmup spec_step below can
            # write its window without outgrowing the throwaway table (a
            # window model's step writes one block)
            table = self.reserve_table(bucket, self.spec_k
                                       if self.spec_enabled else self.window)
            t0 = time.perf_counter()
            tok = self.prefill([1] * bucket, table)
            timings[f'prefill_{bucket}'] = time.perf_counter() - t0
            # one decode step over slot 0 also warms the step shape
            tokens = [tok] + [None] * (self.slots - 1)
            tables = [table] + [None] * (self.slots - 1)
            t0 = time.perf_counter()
            if self.window > 1:
                # a denoising forward: the table keeps nothing of it
                self.window_step(
                    np.ones((self.slots, self.window), np.int64),
                    np.ones((self.slots, self.window), bool),
                    np.ones(self.slots, np.int64), tables,
                    [False] * self.slots)
            else:
                self.decode_step(tokens, tables)
            timings.setdefault('decode_step',
                               time.perf_counter() - t0)
            if self.spec_enabled and not self._spec_compiled:
                base = table.context_len
                feed = [[tok] * (self.spec_k - 1)] \
                    + [None] * (self.slots - 1)
                t0 = time.perf_counter()
                self.spec_step(feed, tables)
                timings['spec_step'] = time.perf_counter() - t0
                table.context_len = base      # roll the warmup feed back
            self.release_table(table)
        return timings
