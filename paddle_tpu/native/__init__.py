"""Native (C++) runtime components, loaded via ctypes (SURVEY §2.12).

- DataPipeline: shuffle buffer + batcher + prefetch ring (the reference's
  C++ BufferedReader/shuffle stack, src/data_pipeline.cc)
- WordPieceTokenizer: BERT-path text preproc (src/wordpiece.cc)
- pack_padded / unpack_padded / bucket_by_length: LoD↔padded conversions
  (src/lod_pack.cc)

The shared library is built from what git tracks: the first load of every
process runs `make` in this directory, which rebuilds when a source is newer
than the binary and is a no-op otherwise, so a stale binary from older
sources is never loaded. Without a toolchain every entry point takes a
pure-Python implementation with identical semantics (one logged warning
says so), so the framework never hard-fails. `is_native()` reports which
path is active.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, 'libpaddle_tpu_native.so')
_lib = None
_tried = False


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    try:
        subprocess.run(['make', '-C', _DIR, '-s'], check=True,
                       capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        # no make/compiler, or the build failed: an existing binary cannot
        # be shown to match the sources, so it is not loaded either
        logging.getLogger(__name__).warning(
            'paddle_tpu.native: `make -C %s` failed (%s: %s%s); using the '
            'pure-Python implementations', _DIR, type(e).__name__, e,
            ('\n' + e.stderr[-1000:]) if getattr(e, 'stderr', None) else '')
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    lib.ptpu_pipeline_create.restype = ctypes.c_void_p
    lib.ptpu_pipeline_create.argtypes = [
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_uint64]
    lib.ptpu_pipeline_push.restype = ctypes.c_int
    lib.ptpu_pipeline_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptpu_pipeline_finish.argtypes = [ctypes.c_void_p]
    lib.ptpu_pipeline_cancel.argtypes = [ctypes.c_void_p]
    lib.ptpu_pipeline_pop.restype = ctypes.c_int64
    lib.ptpu_pipeline_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.ptpu_pipeline_destroy.argtypes = [ctypes.c_void_p]
    lib.ptpu_wp_create.restype = ctypes.c_void_p
    lib.ptpu_wp_create.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                   ctypes.c_int, ctypes.c_char_p]
    lib.ptpu_wp_vocab_size.restype = ctypes.c_int64
    lib.ptpu_wp_vocab_size.argtypes = [ctypes.c_void_p]
    lib.ptpu_wp_lookup.restype = ctypes.c_int64
    lib.ptpu_wp_lookup.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ptpu_wp_tokenize.restype = ctypes.c_int64
    lib.ptpu_wp_tokenize.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64, ctypes.c_void_p,
                                     ctypes.c_int64]
    lib.ptpu_wp_destroy.argtypes = [ctypes.c_void_p]
    for name in ('ptpu_pack_f32', 'ptpu_pack_i64'):
        getattr(lib, name).restype = None
    lib.ptpu_unpack_f32.restype = ctypes.c_int64
    lib.ptpu_unpack_i64.restype = ctypes.c_int64
    lib.ptpu_bucket_by_length.restype = None
    _lib = lib
    return _lib


def is_native():
    return _load() is not None


def _start_feed(target, iterable):
    """Shared producer thread: push until the target cancels, route errors
    into the target so the consumer re-raises them from pop()."""
    def run():
        try:
            for s in iterable:
                if not target.push(s):
                    return          # consumer cancelled
        except BaseException as e:  # propagate to the consumer
            target._set_error(e)
        finally:
            target.finish()         # always unblock the consumer
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


# ---------------------------------------------------------------------------
# DataPipeline
# ---------------------------------------------------------------------------


class DataPipeline:
    """Shuffle + batch + prefetch over fixed-shape samples.

    Samples are numpy arrays of one dtype/shape; `feed(iterable)` runs on a
    background thread; iterate the pipeline to pop ready batches."""

    def __init__(self, sample_shape, dtype='float32', batch_size=32,
                 shuffle_capacity=0, ring_capacity=4, drop_last=False,
                 seed=0):
        self.sample_shape = tuple(int(s) for s in sample_shape)
        self.dtype = np.dtype(dtype)
        self.batch_size = int(batch_size)
        self.drop_last = drop_last
        self._nbytes = int(np.prod(self.sample_shape)) * self.dtype.itemsize
        self._lib = _load()
        self._thread = None
        self._error = None       # producer-thread exception, re-raised in pop
        if self._lib is not None:
            self._h = self._lib.ptpu_pipeline_create(
                self._nbytes, self.batch_size, int(shuffle_capacity),
                int(ring_capacity), int(drop_last), int(seed))
        else:                                    # python fallback
            self._h = None
            self._fb_rng = np.random.RandomState(seed)
            self._fb_buf = []
            self._fb_batches = []
            self._fb_cap = int(shuffle_capacity)
            self._fb_ring_cap = max(int(ring_capacity), 1)
            self._fb_partial = []
            self._fb_done = False
            self._fb_lock = threading.Lock()
            self._fb_cv = threading.Condition(self._fb_lock)

    # -- producer --
    def push(self, sample):
        """Returns False once the pipeline is finished/cancelled (producers
        should stop feeding)."""
        arr = np.asarray(sample)
        if arr.shape != self.sample_shape:
            raise ValueError(f"sample shape {arr.shape} != "
                             f"{self.sample_shape}")
        arr = np.ascontiguousarray(arr, self.dtype)
        if self._h is not None:
            return bool(self._lib.ptpu_pipeline_push(
                self._h, arr.ctypes.data_as(ctypes.c_void_p)))
        with self._fb_cv:
            # backpressure like the native ring: block while full
            self._fb_cv.wait_for(
                lambda: len(self._fb_batches) < self._fb_ring_cap
                or self._fb_done)
            if self._fb_done:
                return False
            if self._fb_cap > 0:
                if len(self._fb_buf) < self._fb_cap:
                    self._fb_buf.append(arr.copy())
                    return True
                j = self._fb_rng.randint(self._fb_cap)
                out, self._fb_buf[j] = self._fb_buf[j], arr.copy()
                self._fb_emit(out)
            else:
                self._fb_emit(arr.copy())
            return True

    def _fb_emit(self, arr):
        self._fb_partial.append(arr)
        if len(self._fb_partial) == self.batch_size:
            self._fb_batches.append(np.stack(self._fb_partial))
            self._fb_partial = []
            self._fb_cv.notify_all()

    def finish(self):
        if self._h is not None:
            self._lib.ptpu_pipeline_finish(self._h)
            return
        with self._fb_cv:
            if self._fb_cap > 0:
                self._fb_rng.shuffle(self._fb_buf)
                for a in self._fb_buf:
                    # honor the ring bound while draining; cancel breaks out
                    self._fb_cv.wait_for(
                        lambda: len(self._fb_batches) < self._fb_ring_cap
                        or self._fb_done)
                    if self._fb_done:
                        break
                    self._fb_emit(a)
                self._fb_buf = []
            if self._fb_partial and not self.drop_last and not self._fb_done:
                self._fb_batches.append(np.stack(self._fb_partial))
            self._fb_partial = []
            self._fb_done = True
            self._fb_cv.notify_all()

    def _set_error(self, e):
        self._error = e

    def cancel(self):
        """Consumer-side early exit: unblock the producer, drop the rest."""
        if self._h is not None:
            self._lib.ptpu_pipeline_cancel(self._h)
            return
        with self._fb_cv:
            self._fb_done = True
            self._fb_cv.notify_all()

    def feed(self, iterable):
        """Run the producer on a background thread (prefetch overlap).
        Producer exceptions are re-raised from pop() rather than dying
        silently in the thread."""
        self._thread = _start_feed(self, iterable)
        return self

    # -- consumer --
    def pop(self):
        """Next batch (n, *sample_shape) or None at end of stream."""
        if self._h is not None:
            out = np.empty((self.batch_size,) + self.sample_shape, self.dtype)
            n = self._lib.ptpu_pipeline_pop(
                self._h, out.ctypes.data_as(ctypes.c_void_p))
            if n == 0:
                if self._error is not None:
                    err, self._error = self._error, None
                    raise err
                return None
            return out[:n]
        with self._fb_cv:
            self._fb_cv.wait_for(
                lambda: self._fb_batches or self._fb_done)
            if not self._fb_batches:
                if self._error is not None:
                    err, self._error = self._error, None
                    raise err
                return None
            b = self._fb_batches.pop(0)
            self._fb_cv.notify_all()    # free producer backpressure
            return b

    def __iter__(self):
        try:
            while True:
                b = self.pop()
                if b is None:
                    return
                yield b
        finally:
            self.cancel()   # early break: unblock the producer

    def __del__(self):
        if getattr(self, '_h', None) is not None and self._lib is not None:
            # the feed thread may still hold the native handle: cancel and
            # join before freeing (avoids use-after-free on the C++ side)
            try:
                self._lib.ptpu_pipeline_cancel(self._h)
                t = getattr(self, '_thread', None)
                # the GC can run __del__ ON the feed thread (e.g. when the
                # last consumer reference dies inside it) — joining the
                # current thread raises
                import threading
                if (t is not None and t.is_alive()
                        and t is not threading.current_thread()):
                    t.join(timeout=5.0)
            finally:
                self._lib.ptpu_pipeline_destroy(self._h)
                self._h = None


# ---------------------------------------------------------------------------
# WordPiece tokenizer
# ---------------------------------------------------------------------------


class WordPieceTokenizer:
    def __init__(self, vocab, lowercase=True, unk_token='[UNK]'):
        """vocab: path to a vocab file, list of tokens, or dict token→id."""
        if isinstance(vocab, str):
            with open(vocab, 'rb') as f:
                blob = f.read()
            # BERT convention: id == line number. Blank lines stay in the
            # list as placeholders so subsequent ids don't shift.
            tokens = blob.decode('utf-8').split('\n')
            if tokens and tokens[-1] == '':
                tokens.pop()  # trailing newline is not a vocab line
        elif isinstance(vocab, dict):
            tokens = [t for t, _ in sorted(vocab.items(),
                                           key=lambda kv: kv[1])]
        else:
            tokens = list(vocab)
        self._tokens = tokens
        self._vocab = {t: i for i, t in enumerate(tokens) if t}
        self.lowercase = lowercase
        self.unk_token = unk_token
        self._lib = _load()
        if self._lib is not None:
            blob = '\n'.join(tokens).encode('utf-8')
            self._h = self._lib.ptpu_wp_create(blob, len(blob),
                                               int(lowercase),
                                               unk_token.encode())
        else:
            self._h = None

    @property
    def vocab_size(self):
        return len(self._tokens)

    def lookup(self, token):
        return self._vocab.get(token, -1)

    def tokenize(self, text, max_len=512):
        if self._h is not None:
            enc = text.encode('utf-8')
            out = np.empty(max_len, np.int64)
            n = self._lib.ptpu_wp_tokenize(
                self._h, enc, len(enc), out.ctypes.data_as(ctypes.c_void_p),
                max_len)
            return out[:n].tolist()
        return self._py_tokenize(text)[:max_len]

    @staticmethod
    def _is_cjk(cp):
        return (0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF or
                0xF900 <= cp <= 0xFAFF or 0x20000 <= cp <= 0x2A6DF or
                0x2A700 <= cp <= 0x2B73F or 0x2B740 <= cp <= 0x2B81F or
                0x2B820 <= cp <= 0x2CEAF or 0x2F800 <= cp <= 0x2FA1F)

    def _py_tokenize(self, text):
        """Matches the C++ tokenizer: ASCII space/punct split + ASCII-only
        lowercasing, non-ASCII chars kept intact, CJK ideographs split off as
        standalone words (BERT BasicTokenizer ranges), 100-byte word cap."""
        import string
        punct = set(string.punctuation)
        space = set(' \t\n\r\v\f')
        unk = self._vocab.get(self.unk_token, 0)
        words = []
        cur = []
        for ch in text:
            if ch in space:
                if cur:
                    words.append(''.join(cur))
                    cur = []
            elif ch in punct:
                if cur:
                    words.append(''.join(cur))
                    cur = []
                words.append(ch)
            elif ord(ch) >= 0x80 and self._is_cjk(ord(ch)):
                if cur:
                    words.append(''.join(cur))
                    cur = []
                words.append(ch)
            else:
                cur.append(ch.lower() if self.lowercase and 'A' <= ch <= 'Z'
                           else ch)
        if cur:
            words.append(''.join(cur))
        ids = []
        for w in words:
            if len(w.encode('utf-8')) > 100:
                ids.append(unk)
                continue
            start, sub, bad = 0, [], False
            while start < len(w):
                end = len(w)
                cur_id = None
                while start < end:
                    piece = ('##' if start > 0 else '') + w[start:end]
                    if piece in self._vocab:
                        cur_id = self._vocab[piece]
                        break
                    end -= 1
                if cur_id is None:
                    bad = True
                    break
                sub.append(cur_id)
                start = end
            ids.extend([unk] if bad else sub)
        return ids

    def __del__(self):
        if getattr(self, '_h', None) is not None and self._lib is not None:
            self._lib.ptpu_wp_destroy(self._h)
            self._h = None


# ---------------------------------------------------------------------------
# LoD / ragged packing
# ---------------------------------------------------------------------------


def pack_padded(flat, lengths, max_len=None, pad_value=0):
    """Concatenated rows (N, D...) + lengths (B,) → padded (B, T, D...)."""
    flat = np.ascontiguousarray(flat)
    lengths = np.ascontiguousarray(lengths, np.int64)
    B = lengths.shape[0]
    T = int(max_len if max_len is not None else lengths.max(initial=0))
    width = int(np.prod(flat.shape[1:])) if flat.ndim > 1 else 1
    lib = _load()
    kind = {np.dtype('float32'): 'f32', np.dtype('int64'): 'i64'}.get(
        flat.dtype)
    if lib is not None and kind is not None:
        out = np.empty((B, T) + flat.shape[1:], flat.dtype)
        fn = getattr(lib, f'ptpu_pack_{kind}')
        fn(flat.ctypes.data_as(ctypes.c_void_p),
           lengths.ctypes.data_as(ctypes.c_void_p),
           ctypes.c_int64(B), ctypes.c_int64(T), ctypes.c_int64(width),
           (ctypes.c_float if kind == 'f32' else ctypes.c_int64)(pad_value),
           out.ctypes.data_as(ctypes.c_void_p))
        return out
    out = np.full((B, T) + flat.shape[1:], pad_value, flat.dtype)
    off = 0
    for b in range(B):
        n = min(int(lengths[b]), T)
        out[b, :n] = flat[off:off + n]
        off += int(lengths[b])
    return out


def unpack_padded(padded, lengths):
    """Padded (B, T, D...) + lengths → concatenated (sum(min(len,T)), D...)."""
    padded = np.ascontiguousarray(padded)
    lengths = np.ascontiguousarray(lengths, np.int64)
    B, T = padded.shape[0], padded.shape[1]
    width = int(np.prod(padded.shape[2:])) if padded.ndim > 2 else 1
    total = int(np.minimum(lengths, T).sum())
    lib = _load()
    kind = {np.dtype('float32'): 'f32', np.dtype('int64'): 'i64'}.get(
        padded.dtype)
    if lib is not None and kind is not None:
        out = np.empty((total,) + padded.shape[2:], padded.dtype)
        fn = getattr(lib, f'ptpu_unpack_{kind}')
        fn(padded.ctypes.data_as(ctypes.c_void_p),
           lengths.ctypes.data_as(ctypes.c_void_p),
           ctypes.c_int64(B), ctypes.c_int64(T), ctypes.c_int64(width),
           out.ctypes.data_as(ctypes.c_void_p))
        return out
    parts = [padded[b, :min(int(lengths[b]), T)] for b in range(B)]
    return np.concatenate(parts, 0) if parts else \
        np.empty((0,) + padded.shape[2:], padded.dtype)


def bucket_by_length(lengths):
    """Stable argsort of lengths, descending (length-bucketed batching)."""
    lengths = np.ascontiguousarray(lengths, np.int64)
    lib = _load()
    if lib is not None:
        out = np.empty(lengths.shape[0], np.int64)
        lib.ptpu_bucket_by_length(
            lengths.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int64(lengths.shape[0]),
            out.ctypes.data_as(ctypes.c_void_p))
        return out
    return np.argsort(-lengths, kind='stable').astype(np.int64)


class TupleDataPipeline:
    """DataPipeline over multi-field samples (img, label, ...): each sample's
    fields are packed into one contiguous byte record so shuffling keeps
    fields aligned; pop() splits batches back into per-field arrays."""

    def __init__(self, field_shapes, field_dtypes, batch_size,
                 shuffle_capacity=0, ring_capacity=4, drop_last=False,
                 seed=0):
        self.shapes = [tuple(int(d) for d in s) for s in field_shapes]
        self.dtypes = [np.dtype(d) for d in field_dtypes]
        self.nbytes = [int(np.prod(s)) * d.itemsize
                       for s, d in zip(self.shapes, self.dtypes)]
        self._pipe = DataPipeline((sum(self.nbytes),), 'uint8', batch_size,
                                  shuffle_capacity, ring_capacity, drop_last,
                                  seed)

    def push(self, fields):
        fields = fields if isinstance(fields, (list, tuple)) else (fields,)
        parts = []
        for i, (f, shape, d) in enumerate(zip(fields, self.shapes,
                                              self.dtypes)):
            a = np.asarray(f)
            if a.shape != shape:
                raise ValueError(
                    f"field {i}: sample shape {a.shape} != {shape} inferred "
                    f"from the first sample (variable-shape samples need "
                    f"padding before batching)")
            if a.dtype != d and a.dtype.kind != d.kind:
                raise TypeError(
                    f"field {i}: sample dtype {a.dtype} incompatible with "
                    f"{d} inferred from the first sample")
            parts.append(np.ascontiguousarray(a, d).view(np.uint8)
                         .reshape(-1))
        return self._pipe.push(np.concatenate(parts) if len(parts) > 1
                               else parts[0])

    def finish(self):
        self._pipe.finish()

    def cancel(self):
        self._pipe.cancel()

    def _set_error(self, e):
        self._pipe._set_error(e)

    def feed(self, iterable):
        self._thread = _start_feed(self, iterable)
        return self

    def pop(self):
        rec = self._pipe.pop()
        if rec is None:
            return None
        n = rec.shape[0]
        out = []
        off = 0
        for shape, dt, nb in zip(self.shapes, self.dtypes, self.nbytes):
            chunk = rec[:, off:off + nb]
            out.append(np.ascontiguousarray(chunk).view(dt).reshape(
                (n,) + shape))
            off += nb
        return tuple(out)

    def __iter__(self):
        try:
            while True:
                b = self.pop()
                if b is None:
                    return
                yield b
        finally:
            self.cancel()
