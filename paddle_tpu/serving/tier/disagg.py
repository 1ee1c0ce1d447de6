"""Disaggregated prefill/decode: prefill-role replicas run the bucket
ladder and ship finished KV blocks + the first greedy token to decode-role
replicas, so long prompts stop stalling the lockstep ``(S, 1)`` decode step
(docs/SERVING.md "Serving tier").

Why split the phases: prefill and decode want opposite shapes. Prefill is
one big bucket-padded forward (compute-bound, O(P²) attention); decode is a
tiny fixed-shape step whose latency IS the per-token latency of every
active stream. Colocated, each admission's prefill runs between decode
steps and every active stream's next token waits behind it. Disaggregated,
the scheduler marks the admitted slot handoff-pending and keeps stepping;
a prefill worker runs the prompt on its OWN engine/pool and hands back a
:class:`KVPayload`; the decode worker injects the whole blocks (one scatter
per layer) and the stream starts.

The HANDOFF INTERFACE is the seam: :class:`LocalPrefillWorker` is the
in-process transport (threads + queues — the form a single-host deployment
uses, and what the parity tests pin); :meth:`KVPayload.to_bytes` /
:meth:`KVPayload.from_bytes` define the wire format a cross-host transport
ships, so a network hop slots in behind the same
``submit``/``drain_completed`` contract without touching the scheduler.

Parity: the prefill engine runs the SAME model weights through the same
prefill program (engine.py keeps the programs per model, keyed by the
pool's geometry), so the shipped K/V bytes are what a colocated prefill
would have written, and the handoff itself moves them byte for byte — the
decoded token stream equals colocated's and the uncached whole-sequence
reference's (tests/framework/test_disagg.py).
"""
from __future__ import annotations

import io
import queue
import threading
import time

import numpy as np

from .. import metrics as _m
from ..errors import ServingError

__all__ = ['KVPayload', 'PrefillReplica', 'LocalPrefillWorker']


class KVPayload:
    """One finished prefill (or one spilled prefix-cache block): whole KV
    blocks for every layer + the first greedy token. ``layers[i]`` is
    ``(k, v)`` with shape (H, num_blocks, block_size, D) — the
    :meth:`KVCachePool.read_blocks` layout, scatter-ready on the decode
    side.

    ``kv_dtype`` records the sender pool's storage dtype
    (``PADDLE_TPU_KV_DTYPE``); for int8 pools ``scales[i]`` is the
    ``(k_scales, v_scales)`` pair of (H, num_blocks, block_size) f32
    row scales (``read_block_scales``) — shipping the quantized payload +
    scales keeps a same-dtype handoff byte-exact AND ~4× smaller on the
    wire than the f32 bytes it replaces."""

    __slots__ = ('layers', 'context_len', 'first_token', 'block_size',
                 'kv_dtype', 'scales')

    def __init__(self, layers, context_len, first_token, block_size,
                 kv_dtype='f32', scales=None):
        self.layers = layers
        self.context_len = int(context_len)
        self.first_token = int(first_token)
        self.block_size = int(block_size)
        self.kv_dtype = kv_dtype
        self.scales = scales          # per-layer (k_scales, v_scales) | None

    @property
    def num_blocks(self):
        return self.layers[0][0].shape[1] if self.layers else 0

    @property
    def nbytes(self):
        total = sum(k.nbytes + v.nbytes for k, v in self.layers)
        if self.scales is not None:
            total += sum(ks.nbytes + vs.nbytes
                         for ks, vs in self.scales if ks is not None)
        return total

    # -- wire format (the cross-host seam) ---------------------------------
    def to_bytes(self):
        from ..decode.kv_cache import KV_DTYPE_CODES
        arrays = {'meta': np.asarray(
            [self.context_len, self.first_token, self.block_size,
             KV_DTYPE_CODES[self.kv_dtype]], np.int64)}
        for i, (k, v) in enumerate(self.layers):
            k, v = np.asarray(k), np.asarray(v)
            if k.dtype.name == 'bfloat16':
                # npz has no portable bf16; ship as f32 (a lossless widen —
                # the receiving pool re-narrows to identical bf16 bytes)
                k, v = k.astype(np.float32), v.astype(np.float32)
            arrays[f'k{i}'] = k
            arrays[f'v{i}'] = v
            if self.scales is not None and self.scales[i] is not None:
                arrays[f'ks{i}'] = np.asarray(self.scales[i][0])
                arrays[f'vs{i}'] = np.asarray(self.scales[i][1])
        buf = io.BytesIO()
        # wire serialization into memory — no file, torn-write-proof
        # commit does not apply
        np.savez(buf, **arrays)  # lint: allow-io (in-memory BytesIO, not a file)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, data):
        from ..decode.kv_cache import KV_DTYPE_CODES
        codes = {v: k for k, v in KV_DTYPE_CODES.items()}
        with np.load(io.BytesIO(data)) as z:
            meta = [int(x) for x in z['meta']]
            ctx, first, bs = meta[:3]
            # pre-quantization senders wrote a 3-int meta: f32 payload
            kv_dtype = codes[meta[3]] if len(meta) > 3 else 'f32'
            layers, scales, any_scales = [], [], False
            i = 0
            while f'k{i}' in z:
                layers.append((z[f'k{i}'], z[f'v{i}']))
                if f'ks{i}' in z:
                    scales.append((z[f'ks{i}'], z[f'vs{i}']))
                    any_scales = True
                else:
                    scales.append(None)
                i += 1
        return cls(layers, ctx, first, bs, kv_dtype=kv_dtype,
                   scales=scales if any_scales else None)


class PrefillReplica:
    """Prefill-role wrapper around a :class:`DecodeEngine`: its pool is
    scratch space — blocks live only from prefill to payload extraction,
    then free. One worker thread owns it (``LocalPrefillWorker``)."""

    def __init__(self, engine):
        # the handoff moves [k, v] blocks of per-head rows: what the
        # engine's layout cannot hand off is refused here (layout.py)
        engine.layout.refuse(handoff=True)
        self.engine = engine

    def prefill_to_payload(self, prompt, max_new_tokens=0):
        """Run the bucket-padded prompt on the prefill engine, read the
        finished blocks out, free them, return the :class:`KVPayload`."""
        eng = self.engine
        bs = eng.pool.block_size
        table = eng.pool.new_table(len(prompt))   # prompt only: scratch use
        try:
            first = eng.prefill(prompt, table)
            nb = -(-len(prompt) // bs)
            layers, scales, any_scales = [], [], False
            for layer in range(eng.pool.num_layers):
                layers.append(eng.pool.read_blocks(layer, table.blocks[:nb]))
                sc = eng.pool.read_block_scales(layer, table.blocks[:nb])
                scales.append(sc)
                any_scales = any_scales or sc is not None
        finally:
            eng.release_table(table)
        return KVPayload(layers, len(prompt), first, bs,
                         kv_dtype=eng.pool.kv_dtype,
                         scales=scales if any_scales else None)


class LocalPrefillWorker:
    """In-process handoff transport: a worker thread pool running
    :class:`PrefillReplica` jobs, feeding a completion queue the decode
    scheduler drains between steps.

    Contract consumed by ``DecodeScheduler(disagg=...)``:

    - ``submit(key, prompt, max_new_tokens)`` — enqueue one prefill; never
      blocks the caller.
    - ``drain_completed(timeout)`` — all finished ``(key, payload, exc)``
      triples; ``exc`` is a typed ServingError when the prefill failed
      (the request fails, the decode loop keeps serving).
    """

    def __init__(self, prefill_replicas, start=True):
        if not isinstance(prefill_replicas, (list, tuple)):
            prefill_replicas = [prefill_replicas]
        self.replicas = [r if isinstance(r, PrefillReplica)
                         else PrefillReplica(r) for r in prefill_replicas]
        self._jobs = queue.Queue()
        self._done = queue.Queue()
        self._closing = False
        self._pending = 0
        self._lock = threading.Lock()
        self._threads = [
            threading.Thread(target=self._run, args=(rep,),
                             name=f'paddle-tpu-prefill-worker-{i}',
                             daemon=True)
            for i, rep in enumerate(self.replicas)]
        if start:
            for t in self._threads:
                t.start()

    @property
    def pending(self):
        with self._lock:
            return self._pending

    def submit(self, key, prompt, max_new_tokens=0):
        with self._lock:
            self._pending += 1
            _m.disagg_pending.set(self._pending)
        self._jobs.put((key, list(prompt), int(max_new_tokens),
                        time.perf_counter()))

    def drain_completed(self, timeout=0.0):
        out = []
        deadline = time.monotonic() + timeout
        while True:
            try:
                remaining = deadline - time.monotonic()
                if out or remaining <= 0:
                    out.append(self._done.get_nowait())
                else:
                    out.append(self._done.get(timeout=remaining))
            except queue.Empty:
                return out

    def _run(self, replica):
        while True:
            job = self._jobs.get()
            if job is None:
                return
            key, prompt, max_new, t0 = job
            payload, exc = None, None
            try:
                payload = replica.prefill_to_payload(prompt, max_new)
            except Exception as e:
                exc = e if isinstance(e, ServingError) else ServingError(
                    f'disaggregated prefill failed: '
                    f'{type(e).__name__}: {e}')
                _m.disagg_handoff_failures.inc()
            with self._lock:
                self._pending -= 1
                _m.disagg_pending.set(self._pending)
            if payload is not None:
                _m.disagg_handoffs.inc()
                _m.disagg_kv_bytes.inc(payload.nbytes)
                _m.disagg_handoff_seconds.observe(time.perf_counter() - t0)
            self._done.put((key, payload, exc))

    def close(self):
        self._closing = True
        for _ in self._threads:
            self._jobs.put(None)
        for t in self._threads:
            t.join(timeout=10)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
