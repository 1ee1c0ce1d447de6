"""Serving metric handles (always-on, unlike training telemetry).

Training instrumentation guards on ``observability._ENABLED`` because the
eager dispatch path is ~10 µs/op; the serving path runs one device call per
*batch* (ms-scale), so a handful of counter increments per request is noise.
More importantly the HTTP ``/metrics`` endpoint must work out of the box —
an operator scraping a serving box should not need PADDLE_TPU_TELEMETRY=1.
So serving records straight into :data:`observability.registry` and shows up
in both its exports alongside whatever the training-side telemetry collected.

Every handle here is a :class:`_LazyMetric` proxy that re-resolves through
the registry ON EACH USE rather than capturing the metric object at import:
``registry.reset()`` (tests, telemetry teardown) drops all metric objects,
and a captured handle would keep counting into an orphan that no longer
appears in any export. The resolve is one dict lookup — noise at ms-scale.

Metric catalog (docs/OBSERVABILITY.md has the full table):

- request lifecycle counters: accepted / rejected_overload / rejected_invalid
  / completed / failed / deadline_missed
- serving_queue_depth gauge (sampled at submit/dequeue)
- serving_queue_wait_seconds / serving_compute_seconds histograms — the
  queue-wait vs compute split is THE batching-knob tuning signal
- serving_batch_rows / serving_padding_waste_ratio histograms — how full the
  coalesced batches are and how much of each padded bucket is thrown away
- per-bucket gauges/counters: serving_bucket_runs (label bucket),
  serving_bucket_compiled, serving_bucket_compile_seconds (warmup/first-use)
"""
from __future__ import annotations

from ..observability import registry

# padding waste is a ratio in [0, 1): linear buckets
_WASTE_BOUNDS = tuple(i / 10.0 for i in range(1, 10))
# batch row counts: powers of two cover any sane bucket ladder
_ROWS_BOUNDS = tuple(float(2 ** i) for i in range(11))


class _LazyMetric:
    """Registry-resolving proxy: same call surface as Counter/Gauge/Histogram
    (inc/set/observe/labels/value), but survives registry.reset()."""

    __slots__ = ('_kind', '_name', '_help', '_bounds')

    def __init__(self, kind, name, help, bounds=None):
        self._kind = kind
        self._name = name
        self._help = help
        self._bounds = bounds

    def _metric(self):
        if self._kind == 'counter':
            return registry.counter(self._name, self._help)
        if self._kind == 'gauge':
            return registry.gauge(self._name, self._help)
        if self._bounds is not None:
            return registry.histogram(self._name, self._help, self._bounds)
        return registry.histogram(self._name, self._help)

    def inc(self, amount=1.0):
        self._metric().inc(amount)

    def set(self, value):
        self._metric().set(value)

    def observe(self, value):
        self._metric().observe(value)

    def labels(self, **labels):
        return self._metric().labels(**labels)

    @property
    def value(self):
        return self._metric().value


requests_accepted = _LazyMetric(
    'counter', 'serving_requests_accepted',
    'requests admitted to the serving queue')
requests_rejected_overload = _LazyMetric(
    'counter', 'serving_requests_rejected_overload',
    'requests rejected by bounded-queue backpressure (Overloaded)')
requests_rejected_invalid = _LazyMetric(
    'counter', 'serving_requests_rejected_invalid',
    'requests rejected by pre-enqueue validation (InvalidRequest)')
requests_completed = _LazyMetric(
    'counter', 'serving_requests_completed',
    'requests answered with results')
requests_failed = _LazyMetric(
    'counter', 'serving_requests_failed',
    'requests failed by an engine/runtime error after admission')
requests_deadline_missed = _LazyMetric(
    'counter', 'serving_requests_deadline_missed',
    'requests dropped because their deadline expired in the queue')

queue_depth = _LazyMetric(
    'gauge', 'serving_queue_depth',
    'requests waiting in the micro-batcher queue')

queue_wait_seconds = _LazyMetric(
    'histogram', 'serving_queue_wait_seconds',
    'enqueue → batch-execution wait per request')
compute_seconds = _LazyMetric(
    'histogram', 'serving_compute_seconds',
    'device call duration per coalesced batch (by padded bucket)')
batch_rows = _LazyMetric(
    'histogram', 'serving_batch_rows',
    'real (unpadded) rows per executed batch', bounds=_ROWS_BOUNDS)
padding_waste_ratio = _LazyMetric(
    'histogram', 'serving_padding_waste_ratio',
    'fraction of the padded bucket that was padding, per executed batch',
    bounds=_WASTE_BOUNDS)

bucket_runs = _LazyMetric(
    'counter', 'serving_bucket_runs', 'executed batches per bucket size')
bucket_compiled = _LazyMetric(
    'gauge', 'serving_bucket_compiled',
    '1 once the bucket shape has been compiled (warmup or first use)')
bucket_compile_seconds = _LazyMetric(
    'gauge', 'serving_bucket_compile_seconds',
    'wall seconds of the bucket\'s first (compiling) run')
http_responses = _LazyMetric(
    'counter', 'serving_http_responses',
    'HTTP front-end responses by status code')
http_handler_cpu_seconds = _LazyMetric(
    'counter', 'http_handler_cpu_seconds',
    'thread-CPU seconds (time.thread_time) of the HTTP side of POST '
    '/generate: each handler thread\'s from entry to its reply\'s last '
    'byte (one increment a request; a streamed reply\'s bytes are the '
    'stream writer\'s, the handler sleeps through them) and the stream '
    'writer thread\'s own, one increment a turn of its loop, nothing per '
    'token. Its rate is the share of one core, and so at most of the one '
    'interpreter, that the HTTP side takes beside the scheduler\'s worker '
    '(socket calls run without the interpreter lock: an upper bound on '
    'the lock held)')
http_stream_writer_wakes = _LazyMetric(
    'counter', 'http_stream_writer_wakes',
    'hand-offs the stream writer took from the decode scheduler: one put '
    'and one wake each, whatever the number of streams a step touched')
http_stream_writer_tokens = _LazyMetric(
    'counter', 'http_stream_writer_tokens',
    'token lines the stream writer formatted for its connections; over '
    'http_stream_writer_wakes, the tokens one wake delivers (a thread a '
    'connection was woken once a token)')
http_stream_writer_sends = _LazyMetric(
    'counter', 'http_stream_writer_sends',
    'non-blocking send calls the stream writer made: one a connection a '
    'hand-off, more only where a socket did not take its bytes at once')

# -- circuit breaker (serving/breaker.py) ----------------------------------
# state encoding: 0 = closed, 1 = half-open (probing), 2 = open (tripped)

breaker_state = _LazyMetric(
    'gauge', 'serving_breaker_state',
    'predict-path circuit breaker state (0 closed / 1 half-open / 2 open)')
breaker_trips = _LazyMetric(
    'counter', 'serving_breaker_trips',
    'predict-path breaker trips (consecutive-failure threshold or failed '
    'probe)')
breaker_rejected = _LazyMetric(
    'counter', 'serving_breaker_rejected',
    'requests rejected fast with EngineUnhealthy while the breaker was open')
breaker_probes = _LazyMetric(
    'counter', 'serving_breaker_probes',
    'half-open probe windows opened after the breaker cooldown')

PREDICT_BREAKER_METRICS = {'state': breaker_state, 'trips': breaker_trips,
                           'rejected': breaker_rejected,
                           'probes': breaker_probes}


# -- stateful decode engine (serving/decode/, docs/SERVING.md) -------------
# Same always-on discipline as the rest of serving: decode steps are
# ms-scale device calls, and /metrics on a generation server must work
# without PADDLE_TPU_TELEMETRY.

# slot occupancy is a ratio in [0, 1]: linear buckets
_OCCUPANCY_BOUNDS = tuple(i / 10.0 for i in range(1, 10))

decode_requests_accepted = _LazyMetric(
    'counter', 'decode_requests_accepted',
    'generation requests admitted to the decode queue')
decode_requests_completed = _LazyMetric(
    'counter', 'decode_requests_completed',
    'generations finished (eos or token budget)')
decode_requests_failed = _LazyMetric(
    'counter', 'decode_requests_failed',
    'generations failed by an engine/runtime error after admission')
decode_requests_rejected_overload = _LazyMetric(
    'counter', 'decode_requests_rejected_overload',
    'generation requests rejected by bounded-queue backpressure')
decode_requests_rejected_invalid = _LazyMetric(
    'counter', 'decode_requests_rejected_invalid',
    'generation requests rejected by pre-enqueue validation')
decode_requests_deadline_missed = _LazyMetric(
    'counter', 'decode_requests_deadline_missed',
    'generation requests dropped because their deadline expired while '
    'waiting for a slot')
decode_queue_depth = _LazyMetric(
    'gauge', 'decode_queue_depth',
    'generation requests waiting for a decode slot')

decode_slots_total = _LazyMetric(
    'gauge', 'decode_slots_total', 'configured lockstep decode slots (S)')
decode_slots_active = _LazyMetric(
    'gauge', 'decode_slots_active',
    'slots holding a live generation, sampled each decode step')
decode_slot_occupancy = _LazyMetric(
    'histogram', 'decode_slot_occupancy',
    'active/total slot ratio per decode step — the continuous-batching '
    'efficiency signal', bounds=_OCCUPANCY_BOUNDS)

decode_cache_blocks_total = _LazyMetric(
    'gauge', 'decode_cache_blocks_total',
    'allocatable KV-cache blocks (pool size minus the scratch block)')
decode_cache_blocks_used = _LazyMetric(
    'gauge', 'decode_cache_blocks_used',
    'KV-cache blocks currently reserved by live generations')

decode_prefill_seconds = _LazyMetric(
    'histogram', 'decode_prefill_seconds',
    'wall seconds per prompt prefill (bucket-padded, one per admission)')
decode_step_seconds = _LazyMetric(
    'histogram', 'decode_step_seconds',
    'wall seconds per lockstep decode step (all S slots) — with '
    'decode_prefill_seconds this is the prefill-vs-decode time split')
decode_steps = _LazyMetric(
    'counter', 'decode_steps', 'lockstep decode steps executed')
# the inside of an engine call and of a worker-thread cycle, always on and
# O(1) per call or request, never per slot per step: ~30 clock reads and ~25
# observations in a cycle of two prefills and a step
decode_engine_phase_seconds = _LazyMetric(
    'histogram', 'decode_engine_phase_seconds',
    'wall seconds per phase of one engine call (labels call=prefill|step|'
    'spec_step, phase=pack|forward|device_wait|logits_copy|sample); the '
    'phases of a call tile it: forward is the dispatch of the call\'s one '
    'XLA program, device_wait the device running it')
decode_engine_phase_cpu_seconds = _LazyMetric(
    'counter', 'decode_engine_phase_cpu_seconds',
    'thread-CPU seconds (time.thread_time, which counts the calling thread '
    'only while it runs) per phase of the engine calls, same labels and '
    'stamps as decode_engine_phase_seconds: that histogram\'s sum less '
    'this is the time the thread was OFF the CPU in the phase: waiting for '
    'the interpreter lock, for the device, or in a blocking call')
decode_logits_bytes_copied = _LazyMetric(
    'counter', 'decode_logits_bytes_copied',
    'bytes engine calls copied from the device to the host for the pick: '
    'the int32 ids, and the logits rows where a call asked for them')
decode_expert_assignments = _LazyMetric(
    'counter', 'decode_expert_assignments',
    'token-to-expert assignments of the calls\' live tokens, over every '
    'routed-expert layer (a rung\'s padding and idle slots not counted)')
decode_experts_touched = _LazyMetric(
    'counter', 'decode_experts_touched',
    'experts given at least one live token, summed over layers and engine '
    'calls: what a call needs to read of the expert weights')
decode_expert_load_max_over_mean = _LazyMetric(
    'histogram', 'decode_expert_load_max_over_mean',
    'per engine call (label call), the worst layer\'s largest expert load '
    'over its mean load', bounds=(1.0, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0,
                                   16.0, 32.0, 64.0, 128.0))
decode_expert_assignments_total = _LazyMetric(
    'counter', 'decode_expert_assignments_total',
    'a model whose expert layers hold a SHARE of their experts: every '
    'token-to-expert assignment of the calls\' live tokens, to an expert '
    'held here or elsewhere')
decode_expert_assignments_held = _LazyMetric(
    'counter', 'decode_expert_assignments_held',
    'of decode_expert_assignments_total, those to an expert this model '
    'holds: the ones computed here (held experts / the router\'s width of '
    'them, if the router spreads evenly)')
decode_full_blocks_held = _LazyMetric(
    'gauge', 'decode_full_blocks_held',
    'a model with layer classes: blocks of the FULL class held by live '
    'requests (a table that grows with the context)')
decode_sliding_blocks_held = _LazyMetric(
    'gauge', 'decode_sliding_blocks_held',
    'a model with layer classes: blocks of the SLIDING class held by live '
    'requests (a ring a request, never more than span / block + 1)')
decode_kv_positions_held = _LazyMetric(
    'counter', 'decode_kv_positions_held',
    'a model with layer classes: positions its layers hold of the live '
    'contexts, per prefill and per step: context a full layer, '
    'min(context, span) a sliding layer, summed over slots and layers')
decode_kv_positions_if_unwindowed = _LazyMetric(
    'counter', 'decode_kv_positions_if_unwindowed',
    'what decode_kv_positions_held would count were every layer full: '
    'context x layers; 1 - held / this is what the ring gives back')
decode_context_positions_read = _LazyMetric(
    'counter', 'decode_context_positions_read',
    'cached positions a decode step attends: the live context of every '
    'active slot, summed over layers and steps')
decode_kv_blocks_read = _LazyMetric(
    'counter', 'decode_kv_blocks_read',
    'cache blocks a decode step\'s attention reads took from the pool, '
    'summed over layers and steps: the live blocks the lockstep step '
    'walked, its last chunk\'s padding included; S x max_blocks a layer '
    'for a read that gathers every slot\'s whole table')
decode_state_updates = _LazyMetric(
    'counter', 'decode_state_updates',
    'recurrent states a decode step advanced: live slots x state layers, '
    'summed over steps (idle slots advance the scratch row, not counted)')
decode_state_tokens_folded = _LazyMetric(
    'counter', 'decode_state_tokens_folded',
    'prompt tokens a prefill folded into a recurrent state: prompt length '
    'x state layers, summed over prefills (a rung\'s padding not counted)')
decode_conv_rows = _LazyMetric(
    'counter', 'decode_conv_rows_total',
    'live rows through the gated short convolutions: prompt length x conv '
    'layers a prefill (a rung\'s padding not counted), live slots x conv '
    'layers a decode step')
# block diffusion (a window model: serving/decode/engine.py::window_step)
decode_diffusion_denoise_forwards = _LazyMetric(
    'counter', 'decode_diffusion_denoise_forwards',
    'live slot-forwards of a window model that denoised a block: the '
    'block\'s B rows fed over the cache, positions unmasked from their '
    'confidences, the K/V written not kept (idle slots not counted)')
decode_diffusion_commit_forwards = _LazyMetric(
    'counter', 'decode_diffusion_commit_forwards',
    'live slot-forwards of a window model that committed a block: the '
    'finished block fed once more, its K/V kept, its rows\' picks unused')
decode_diffusion_tokens_committed = _LazyMetric(
    'counter', 'decode_diffusion_tokens_committed',
    'answer tokens emitted at a block\'s commit: the block\'s positions '
    'less a prompt\'s tail in the first block and what the asked length '
    'cuts off the last')
decode_block_seconds = _LazyMetric(
    'histogram', 'decode_block_seconds',
    'wall seconds of one block of a window model, from the start of its '
    'first denoising forward to the end of its commit forward')
decode_scheduler_phase_seconds = _LazyMetric(
    'histogram', 'decode_scheduler_phase_seconds',
    'wall seconds of the scheduler worker thread per loop iteration (label '
    'phase: cycle = the whole iteration; inside it admit = the locked '
    'expire-and-admit pass, engine = its engine calls, emit = what follows '
    'an engine call that returned tokens, once per call: the per-slot loop '
    'after a step, the first token after a prefill; wait = blocked idle; '
    'book = the thread\'s bookkeeping between those, every stretch under '
    'neither admit, an engine call\'s phases, emit nor wait, summed per '
    'iteration); self time = cycle - wait - engine; cycle = admit + the '
    'engine calls\' phases + emit + book + wait')
decode_scheduler_phase_cpu_seconds = _LazyMetric(
    'counter', 'decode_scheduler_phase_cpu_seconds',
    'thread-CPU seconds (time.thread_time) of the scheduler worker thread '
    'per phase, same labels and stamps as decode_scheduler_phase_seconds: '
    'wall less CPU of a phase is the time the thread was off the CPU in '
    'it, which in admit, emit and book (pure interpreter work) is the wait '
    'for the interpreter lock')
decode_queue_wait_seconds = _LazyMetric(
    'histogram', 'decode_queue_wait_seconds',
    'accepted by submit -> admitted to a slot, per generation (every '
    'request, traced or not)')
decode_tokens_generated = _LazyMetric(
    'counter', 'decode_tokens_generated',
    'tokens emitted to generation streams (rate = tokens/s)')
decode_prefill_compiles = _LazyMetric(
    'counter', 'decode_prefill_compiles',
    'prefill rungs an engine ran for the first time, each one program '
    '(bounded by the prompt ladder length)')

# speculative decoding (engine.spec_step + scheduler verify loop); accept
# length per round is a small integer — linear buckets up to the window
_ACCEPT_BOUNDS = tuple(float(i) for i in range(9))

decode_spec_rounds = _LazyMetric(
    'counter', 'decode_spec_rounds',
    'speculative (S, k) verify steps executed (each replaces up to k '
    'lockstep steps)')
decode_spec_draft_tokens = _LazyMetric(
    'counter', 'decode_spec_draft_tokens',
    'draft tokens proposed to verify rounds across all slots')
decode_spec_accepted_tokens = _LazyMetric(
    'counter', 'decode_spec_accepted_tokens',
    'draft tokens accepted by the target model (longest matching prefix); '
    'accepted/draft is the acceptance rate')
decode_spec_acceptance = _LazyMetric(
    'gauge', 'decode_spec_acceptance',
    'cumulative draft-token acceptance rate (accepted / proposed)')
decode_spec_verify_seconds = _LazyMetric(
    'histogram', 'decode_spec_verify_seconds',
    'wall seconds per batched (S, k) verify step — the verify-step split '
    'of decode time')
decode_spec_accept_len = _LazyMetric(
    'histogram', 'decode_spec_accept_len',
    'tokens emitted per slot per verify round (1 = all drafts rejected)',
    bounds=_ACCEPT_BOUNDS)
decode_tokens_sampled = _LazyMetric(
    'counter', 'decode_tokens_sampled',
    'tokens drawn through per-request sampling (temperature > 0) rather '
    'than greedy argmax')

decode_breaker_state = _LazyMetric(
    'gauge', 'decode_breaker_state',
    'decode-path circuit breaker state (0 closed / 1 half-open / 2 open)')
decode_breaker_trips = _LazyMetric(
    'counter', 'decode_breaker_trips',
    'decode-path breaker trips (consecutive-failure threshold or failed '
    'probe)')
decode_breaker_rejected = _LazyMetric(
    'counter', 'decode_breaker_rejected',
    'generation requests rejected fast with EngineUnhealthy while the '
    'decode breaker was open')
decode_breaker_probes = _LazyMetric(
    'counter', 'decode_breaker_probes',
    'half-open probe windows opened after the decode breaker cooldown')

DECODE_BREAKER_METRICS = {'state': decode_breaker_state,
                          'trips': decode_breaker_trips,
                          'rejected': decode_breaker_rejected,
                          'probes': decode_breaker_probes}


# -- serving tier (serving/tier/, docs/SERVING.md "Serving tier") ----------
# Same always-on discipline: the router/cache/handoff paths run per-request
# (ms-scale), and an operator scraping a router box must see these without
# PADDLE_TPU_TELEMETRY.

# radix prefix cache over the paged KV pool (tier/prefix_cache.py)
prefix_cache_hits = _LazyMetric(
    'counter', 'prefix_cache_hits',
    'admissions that matched >= 1 whole cached block of their prompt')
prefix_cache_misses = _LazyMetric(
    'counter', 'prefix_cache_misses',
    'admissions with no cached prefix (cold prompts)')
prefix_cache_tokens_saved = _LazyMetric(
    'counter', 'prefix_cache_tokens_saved',
    'prompt tokens served from cached KV blocks instead of prefill '
    'compute — the prefill-compute-saved signal')
prefix_cache_blocks_resident = _LazyMetric(
    'gauge', 'prefix_cache_blocks_resident',
    'KV blocks currently held resident by the prefix-cache trie')
prefix_cache_inserted_blocks = _LazyMetric(
    'counter', 'prefix_cache_inserted_blocks',
    'whole prompt blocks published into the trie')
prefix_cache_evicted_blocks = _LazyMetric(
    'counter', 'prefix_cache_evicted_blocks',
    'cached blocks evicted (LRU over refcount-idle leaves) under pool or '
    'cap pressure')
prefix_cache_evictions = _LazyMetric(
    'counter', 'prefix_cache_evictions',
    'blocks leaving HBM residency (spilled or dropped), labeled by cause: '
    'pressure = allocation ran dry, cap = publish hit '
    'PADDLE_TPU_PREFIX_CACHE_MAX_BLOCKS')

# quantized + tiered KV cache (PADDLE_TPU_KV_DTYPE storage dtype + the
# PADDLE_TPU_PREFIX_CACHE_HOST_MB host spill tier — docs/SERVING.md
# "Tiered KV cache")
kv_cache_dtype = _LazyMetric(
    'gauge', 'kv_cache_dtype',
    'KV pool storage dtype code (0 = f32, 1 = bf16, 2 = int8)')
kv_cache_bytes_in_hbm = _LazyMetric(
    'gauge', 'kv_cache_bytes_in_hbm',
    'resident KV pool bytes across allocated layers (payload arrays plus '
    'int8 row-scale arrays), sampled after pool writes')
kv_cache_row_bytes = _LazyMetric(
    'gauge', 'kv_cache_row_bytes',
    'resident bytes of one token\'s cached state in one layer (K and V '
    'rows of every head, or one latent row)')
state_cache_bytes_in_hbm = _LazyMetric(
    'gauge', 'state_cache_bytes_in_hbm',
    'resident bytes of the recurrent-state layers: every row (one a slot '
    'and the scratch row) of every state layer, float32')
state_cache_rows_total = _LazyMetric(
    'gauge', 'state_cache_rows_total',
    'state rows a request can hold (one a slot; the scratch row not '
    'counted)')
state_cache_rows_used = _LazyMetric(
    'gauge', 'state_cache_rows_used',
    'state rows held by live requests')
kv_cache_bytes_spilled = _LazyMetric(
    'counter', 'kv_cache_bytes_spilled',
    'serialized KV payload bytes moved from HBM to the host spill tier')
kv_cache_spill_count = _LazyMetric(
    'counter', 'kv_cache_spill_count',
    'prefix-cache blocks spilled to host RAM instead of being dropped')
kv_cache_reinject_count = _LazyMetric(
    'counter', 'kv_cache_reinject_count',
    'spilled blocks re-scattered into HBM on a later radix hit')
kv_cache_reinject_seconds = _LazyMetric(
    'histogram', 'kv_cache_reinject_seconds',
    'wall seconds per host->HBM reinjection (deserialize + one block '
    'scatter per layer for the whole reinjected run)')

# multi-replica router (tier/router.py)
router_requests = _LazyMetric(
    'counter', 'router_requests', 'generation requests entering the router')
router_requests_completed = _LazyMetric(
    'counter', 'router_requests_completed',
    'routed requests that finished (done line / full reply)')
router_requests_failed = _LazyMetric(
    'counter', 'router_requests_failed',
    'routed requests that failed after streaming began (in-flight on a '
    'dying replica) or exhausted every replica')
router_requests_rerouted = _LazyMetric(
    'counter', 'router_requests_rerouted',
    'dispatch attempts moved to another replica before first byte '
    '(connection refused / 503 / replica died pre-stream) — the '
    'zero-drop failover counter')
router_no_replica = _LazyMetric(
    'counter', 'router_no_replica',
    'pick attempts that found no routable replica (all cold, draining, '
    'degraded, or dead)')
router_replicas_routable = _LazyMetric(
    'gauge', 'router_replicas_routable',
    'replicas currently healthy + warm + not draining')
router_replica_inflight = _LazyMetric(
    'gauge', 'router_replica_inflight',
    'router-side in-flight requests per replica (label replica)')
router_dispatch_seconds = _LazyMetric(
    'histogram', 'router_dispatch_seconds',
    'submit -> replica response headers per dispatch attempt')
router_health_polls = _LazyMetric(
    'counter', 'router_health_polls', 'replica /healthz polls issued')
router_probes = _LazyMetric(
    'counter', 'router_probes',
    'requests routed to a half-open (probing) replica to re-admit it')
router_rolling_restarts = _LazyMetric(
    'counter', 'router_rolling_restarts',
    'replicas restarted behind a drain by rolling_restart()')

# elastic autoscaler (elastic/autoscaler.py; docs/SERVING.md "Autoscaler")
autoscale_decisions = _LazyMetric(
    'counter', 'autoscale_decisions',
    'autoscaler decisions taken (labels action=up|down, trigger='
    'queue_depth|ttft_p99|occupancy|min_replicas)')
autoscale_replicas = _LazyMetric(
    'gauge', 'autoscale_replicas',
    'replicas under autoscaler management (including cold pending ones, '
    'excluding draining-for-retirement ones)')
autoscale_replicas_routable = _LazyMetric(
    'gauge', 'autoscale_replicas_routable',
    'managed replicas currently healthy + warm + not draining')
autoscale_time_to_routable_seconds = _LazyMetric(
    'histogram', 'autoscale_time_to_routable_seconds',
    'scale-up launch -> replica routable (spawn + warmup gate + fast '
    'initial health poll)')
autoscale_drain_seconds = _LazyMetric(
    'histogram', 'autoscale_drain_seconds',
    'scale-down drain start -> replica idle (router in-flight 0 and '
    'replica queue empty) and retired')

# fleet-wide observability (PR 17, docs/OBSERVABILITY.md "Fleet-wide")
decode_ttft_seconds = _LazyMetric(
    'histogram', 'decode_ttft_seconds',
    'submit -> first emitted token per generation (time-to-first-token)')
router_scrape_failures = _LazyMetric(
    'counter', 'router_scrape_failures',
    'replica /metrics scrapes that failed or timed out during a '
    '/metrics/fleet aggregation (label replica)')
router_fleet_scrapes = _LazyMetric(
    'counter', 'router_fleet_scrapes',
    '/metrics/fleet aggregations served')
trace_requests_sampled = _LazyMetric(
    'counter', 'trace_requests_sampled',
    'requests that carried (router) or received (replica) a sampled '
    'trace context')
trace_spans_recorded = _LazyMetric(
    'counter', 'trace_spans_recorded',
    'distributed-trace spans recorded by this process')
trace_clock_offset_seconds = _LazyMetric(
    'gauge', 'trace_clock_offset_seconds',
    'estimated replica-minus-router wall-clock offset from the health '
    'handshake (label replica) — the trace-merge alignment input')

# disaggregated prefill/decode (tier/disagg.py)
disagg_handoffs = _LazyMetric(
    'counter', 'disagg_handoffs',
    'prefill->decode KV handoffs completed')
disagg_handoff_failures = _LazyMetric(
    'counter', 'disagg_handoff_failures',
    'handoffs that failed (prefill error); the request fails typed, the '
    'decode loop keeps stepping')
disagg_handoff_seconds = _LazyMetric(
    'histogram', 'disagg_handoff_seconds',
    'admission -> KV blocks injected into the decode pool, per handoff')
disagg_kv_bytes = _LazyMetric(
    'counter', 'disagg_kv_bytes',
    'KV payload bytes shipped from prefill to decode replicas')
disagg_pending = _LazyMetric(
    'gauge', 'disagg_pending',
    'admitted requests waiting on a prefill handoff right now')
