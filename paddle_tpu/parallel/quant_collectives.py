"""Block-quantized collectives: bytes-on-wire reduction for gradient sync.

Gradient all-reduce is the scale-out bottleneck (ROADMAP item 3): every
DP/FSDP/local-SGD/geo-SGD sync point ran a full-precision ``lax.psum``.
Following EQuARX (PAPERS.md, arxiv 2506.17615), this module provides
block-quantized all-reduce variants that cut wire bytes ~4x (int8) or 2x
(bf16) with a bounded, documented error, expressed entirely in lax
collectives so XLA schedules them on ICI like any other comm:

    quantize local chunks -> all-to-all (the reduce-scatter phase)
    -> dequantize + sum partials in f32 -> requantize
    -> all-gather -> dequantize

Two properties are load-bearing:

- the partial-sum arithmetic is EXACT f32 — only the two codec stages
  lose bits, so the elementwise error is bounded by
  ``sum_i absmax_i(block)/254 + absmax_reduced(block)/254`` (int8,
  round-to-nearest symmetric; see docs/DISTRIBUTED.md for the contract);
- when the mesh axis has size 1, or ``comm_dtype`` resolves to ``f32``,
  every entry point is an EXACT passthrough to the plain lax collective —
  bitwise-identical to the pre-quantization code paths.

Selection is one knob: ``PADDLE_TPU_COMM_DTYPE`` (env, wins) /
``DistributedStrategy.comm_dtype`` / a per-call ``comm_dtype=`` argument,
each in {f32, bf16, int8} — unknown values raise ``ValueError`` naming
the supported set (the PR 8 strict-parse convention).

Telemetry (``PADDLE_TPU_TELEMETRY``): host-side call sites record
``collective_sync_calls`` / ``collective_bytes_on_wire`` /
``collective_bytes_f32_equiv`` counters and a
``collective_quant_rel_error`` round-trip error histogram — the
jit-traced collectives themselves stay pure (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import os

import numpy as np
import jax.numpy as jnp
from jax import lax

from .. import observability as _obs

__all__ = ['SUPPORTED_COMM_DTYPES', 'resolve_comm_dtype', 'block_quantize',
           'block_dequantize', 'qallreduce_sum', 'qallreduce_mean',
           'qreduce_scatter_sum', 'wire_bytes', 'record_collective',
           'quant_error_stats', 'DEFAULT_BLOCK_SIZE', 'rowwise_quantize',
           'rowwise_dequantize', 'sparse_allgather', 'sparse_wire_bytes',
           'record_sparse_collective']

SUPPORTED_COMM_DTYPES = ('f32', 'bf16', 'int8')
DEFAULT_BLOCK_SIZE = 256
ENV_COMM_DTYPE = 'PADDLE_TPU_COMM_DTYPE'


def _validate(value, source):
    if value not in SUPPORTED_COMM_DTYPES:
        raise ValueError(
            f"{source}: unknown comm_dtype {value!r} "
            f"(supported: {', '.join(SUPPORTED_COMM_DTYPES)})")
    return value


def resolve_comm_dtype(value=None):
    """One comm-dtype knob for every sync point. Precedence:
    ``PADDLE_TPU_COMM_DTYPE`` env > the ``value`` argument (a per-call
    override or ``DistributedStrategy.comm_dtype``) > ``'f32'``. Unknown
    names raise ValueError listing the supported set."""
    env = os.environ.get(ENV_COMM_DTYPE)
    if env is not None and env != '':
        return _validate(env, ENV_COMM_DTYPE)
    if value is not None:
        return _validate(value, 'comm_dtype')
    return 'f32'


# ---------------------------------------------------------------------------
# codec: symmetric per-block int8 / plain bf16
# ---------------------------------------------------------------------------

def _padded_size(size, block_size):
    return -(-size // block_size) * block_size


def block_quantize(x, block_size=DEFAULT_BLOCK_SIZE):
    """Symmetric round-to-nearest int8 quantization with one f32 scale per
    ``block_size`` contiguous elements of the flattened input.

    Returns ``(q, scales)``: ``q`` is int8 of shape ``(padded,)`` where
    ``padded`` rounds ``x.size`` up to a whole number of blocks (the tail
    pads with zeros — exact under the zero-maps-to-zero codec), ``scales``
    is f32 of shape ``(padded // block_size,)``. An all-zero block gets
    scale 0 and decodes to exact zeros; a single-element tensor is exact
    (its own absmax maps to ±127)."""
    f = jnp.ravel(x).astype(jnp.float32)
    size = f.shape[0]
    padded = _padded_size(max(size, 1), block_size)
    if padded != size:
        f = jnp.pad(f, (0, padded - size))
    b = f.reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(b), axis=1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(b * inv[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale


def block_dequantize(q, scales, shape=None, block_size=DEFAULT_BLOCK_SIZE):
    """Inverse of :func:`block_quantize`. ``shape`` (when given) slices the
    padding tail off and reshapes to the original tensor shape."""
    f = (q.reshape(-1, block_size).astype(jnp.float32)
         * jnp.asarray(scales, jnp.float32)[:, None]).reshape(-1)
    if shape is not None:
        size = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
        f = f[:size].reshape(shape)
    return f


def _encode(flat, comm_dtype, block_size):
    """flat f32 (block-aligned) -> (payload, scales or None)."""
    if comm_dtype == 'int8':
        return block_quantize(flat, block_size)
    # bf16 carries its own exponent; no block scales needed
    return flat.astype(jnp.bfloat16), None


def _decode(payload, scales, comm_dtype, block_size):
    if comm_dtype == 'int8':
        return block_dequantize(payload, scales, block_size=block_size)
    return payload.astype(jnp.float32)


def rowwise_quantize(vals):
    """Symmetric int8 with ONE f32 scale per embedding row — the sparse
    push codec (docs/SPARSE.md). Unlike :func:`block_quantize`, scales
    align with COO rows so a gathered (rows, vals, scales) triple stays
    row-addressable; an all-zero row (COO padding) gets scale 0 and
    decodes to exact zeros."""
    v = jnp.asarray(vals, jnp.float32)
    absmax = jnp.max(jnp.abs(v), axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    q = jnp.clip(jnp.round(v * inv[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def rowwise_dequantize(q, scales):
    return q.astype(jnp.float32) * jnp.asarray(scales, jnp.float32)[..., None]


def sparse_allgather(rows, vals, axis='dp', comm_dtype=None):
    """The DP sparse gradient push: every device contributes its padded
    COO (rows, vals); each gets the CONCATENATION of all peers' entries
    back — O(n·K·D) bytes at the comm dtype instead of the O(V·D) dense
    all-reduce it replaces. Call inside shard_map/pjit with ``axis``
    bound; the caller coalesces (duplicate rows across peers sum there,
    which IS the gradient reduction). int8 payloads cross the wire with
    per-row f32 scales (exact-zero padding rows survive)."""
    comm = resolve_comm_dtype(comm_dtype)
    n = _axis_size(axis)
    rows = jnp.asarray(rows).astype(jnp.int32)
    vals = jnp.asarray(vals)
    if n == 1:
        return rows, vals.astype(jnp.float32)
    rows_all = lax.all_gather(rows, axis).reshape(-1)
    if comm == 'int8':
        q, s = rowwise_quantize(vals)
        qg = lax.all_gather(q, axis).reshape(-1, vals.shape[-1])
        sg = lax.all_gather(s, axis).reshape(-1)
        return rows_all, rowwise_dequantize(qg, sg)
    if comm == 'bf16':
        vg = lax.all_gather(vals.astype(jnp.bfloat16), axis)
        return rows_all, vg.reshape(-1, vals.shape[-1]).astype(jnp.float32)
    vg = lax.all_gather(vals.astype(jnp.float32), axis)
    return rows_all, vg.reshape(-1, vals.shape[-1])


def sparse_wire_bytes(num_rows, dim, comm_dtype, axis_size):
    """Logical payload bytes one device's COO contribution puts on the
    wire in a :func:`sparse_allgather`: int32 row ids + vals at the codec
    width (+ per-row f32 scales for int8). Axis size 1 moves nothing."""
    comm = resolve_comm_dtype(comm_dtype)
    if axis_size <= 1:
        return 0
    r, d = int(num_rows), int(dim)
    ids = r * 4
    if comm == 'int8':
        return ids + r * d + r * 4
    if comm == 'bf16':
        return ids + r * d * 2
    return ids + r * d * 4


def record_sparse_collective(path, num_rows, dim, comm_dtype, axis_size,
                             dense_elems):
    """Count one sparse push: bytes on wire at the COO+codec size, f32
    equivalent = the dense all-reduce of the ``dense_elems``-element
    table this push replaced — their ratio is the headline sparse win
    (tests/ops/test_sparse_ops.py holds its arithmetic). No-op with
    telemetry off."""
    if not _obs._ENABLED:
        return
    comm = resolve_comm_dtype(comm_dtype)
    _obs.inc('collective_sync_calls', 1,
             help='gradient/param sync collectives by path and comm dtype',
             path=path, dtype=comm)
    _obs.inc('collective_bytes_on_wire',
             sparse_wire_bytes(num_rows, dim, comm, axis_size),
             help='logical collective payload bytes at the wire dtype',
             path=path, dtype=comm)
    _obs.inc('collective_bytes_f32_equiv',
             wire_bytes(dense_elems, 'f32', axis_size, phases=2),
             help='f32-equivalent bytes for the same syncs (ratio = '
                  'compression)',
             path=path)


# ---------------------------------------------------------------------------
# collectives (call inside shard_map/pjit-traced code, axis bound)
# ---------------------------------------------------------------------------

def _axis_size(axis):
    # psum of a concrete scalar is folded to the axis size at trace time
    return int(lax.psum(1, axis))


def qallreduce_sum(x, axis='dp', comm_dtype=None, block_size=None):
    """All-reduce-sum of ``x`` over mesh axis ``axis`` with the comm payload
    block-quantized to ``comm_dtype``.

    EQuARX two-phase decomposition: each device quantizes its local copy in
    chunks, an all-to-all routes chunk i of every peer to device i (the
    reduce-scatter phase at 1/4 or 1/2 the f32 bytes), partials dequantize
    and sum EXACTLY in f32, the reduced chunk requantizes, and an
    all-gather rebuilds the full tensor everywhere. Exact f32 passthrough
    (plain ``lax.psum``, bitwise-identical to pre-quantization code) when
    the axis size is 1 or ``comm_dtype`` resolves to ``'f32'``."""
    comm = resolve_comm_dtype(comm_dtype)
    block_size = int(block_size or DEFAULT_BLOCK_SIZE)
    n = _axis_size(axis)
    if comm == 'f32' or n == 1:
        return lax.psum(x, axis)
    x = jnp.asarray(x)
    shape, dtype = x.shape, x.dtype
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    # pad so every device-destined chunk is a whole number of blocks
    chunk = _padded_size(-(-size // n), block_size)
    padded = chunk * n
    f = jnp.ravel(x).astype(jnp.float32)
    if padded != size:
        f = jnp.pad(f, (0, padded - size))
    # phase 1 — reduce-scatter: quantize, all-to-all, exact f32 partial sum
    payload, scales = _encode(f, comm, block_size)
    pc = lax.all_to_all(payload.reshape(n, chunk), axis,
                        split_axis=0, concat_axis=0)
    if scales is not None:
        sc = lax.all_to_all(scales.reshape(n, chunk // block_size), axis,
                            split_axis=0, concat_axis=0)
        part = (pc.reshape(n, chunk // block_size, block_size)
                .astype(jnp.float32) * sc[:, :, None]).reshape(n, chunk)
    else:
        part = pc.astype(jnp.float32)
    reduced = jnp.sum(part, axis=0)
    # phase 2 — all-gather the requantized reduced chunk
    payload2, scales2 = _encode(reduced, comm, block_size)
    pg = lax.all_gather(payload2, axis)
    if scales2 is not None:
        sg = lax.all_gather(scales2, axis)
        out = (pg.reshape(padded // block_size, block_size)
               .astype(jnp.float32)
               * sg.reshape(-1)[:, None]).reshape(-1)
    else:
        out = pg.reshape(-1).astype(jnp.float32)
    if padded != size:
        out = out[:size]
    return out.reshape(shape).astype(dtype)


def qallreduce_mean(x, axis='dp', comm_dtype=None, block_size=None):
    """All-reduce-mean counterpart of :func:`qallreduce_sum` (exact
    ``lax.pmean`` passthrough at f32 / axis size 1)."""
    comm = resolve_comm_dtype(comm_dtype)
    n = _axis_size(axis)
    if comm == 'f32' or n == 1:
        return lax.pmean(x, axis)
    s = qallreduce_sum(x, axis, comm_dtype=comm, block_size=block_size)
    return (s / n).astype(jnp.asarray(x).dtype)


def qreduce_scatter_sum(x, axis='dp', comm_dtype=None, block_size=None,
                        scattered_dimension=0):
    """Reduce-scatter-sum with a quantized payload: phase 1 of the EQuARX
    decomposition alone — each device ends with its 1/n tile of the sum
    along ``scattered_dimension`` (``lax.psum_scatter(..., tiled=True)``
    semantics; exact f32 passthrough at f32 / axis size 1). This is the
    gradient half of ZeRO/FSDP sync: the summed partials never exist in
    full precision on the wire, only the local tile does."""
    comm = resolve_comm_dtype(comm_dtype)
    block_size = int(block_size or DEFAULT_BLOCK_SIZE)
    n = _axis_size(axis)
    d = scattered_dimension
    if comm == 'f32' or n == 1:
        return lax.psum_scatter(x, axis, scatter_dimension=d, tiled=True)
    x = jnp.asarray(x)
    if x.shape[d] % n:
        raise ValueError(
            f"qreduce_scatter_sum: dim {d} of shape {x.shape} is not "
            f"divisible by the axis size {n}")
    dtype = x.dtype
    moved = jnp.moveaxis(x, d, 0)
    tile_shape = (moved.shape[0] // n,) + moved.shape[1:]
    piece = int(np.prod(tile_shape, dtype=np.int64))
    padded = _padded_size(piece, block_size)
    flat = moved.reshape(n, piece).astype(jnp.float32)
    if padded != piece:
        flat = jnp.pad(flat, ((0, 0), (0, padded - piece)))
    # block boundaries stay inside one device-destined piece (padded is a
    # whole number of blocks), so per-piece scales survive the all-to-all
    payload, scales = _encode(flat.reshape(-1), comm, block_size)
    pc = lax.all_to_all(payload.reshape(n, padded), axis,
                        split_axis=0, concat_axis=0)
    if scales is not None:
        sc = lax.all_to_all(scales.reshape(n, padded // block_size), axis,
                            split_axis=0, concat_axis=0)
        part = (pc.reshape(n, padded // block_size, block_size)
                .astype(jnp.float32) * sc[:, :, None]).reshape(n, padded)
    else:
        part = pc.astype(jnp.float32)
    tile = jnp.sum(part, axis=0)[:piece].reshape(tile_shape)
    return jnp.moveaxis(tile, 0, d).astype(dtype)


# ---------------------------------------------------------------------------
# bytes-on-wire accounting + quantization-error telemetry (host side)
# ---------------------------------------------------------------------------

def wire_bytes(num_elements, comm_dtype, axis_size, block_size=None,
               phases=2):
    """Logical payload bytes a collective over ``num_elements`` puts on the
    wire per device: ``phases`` passes over the (block-padded) tensor at
    the codec's width, plus the f32 scale sidecar for int8. The f32
    baseline is the same two-pass (reduce-scatter + all-gather) accounting
    so the int8/f32 ratio is the EQuARX compression, not a phase-count
    artifact. Axis size 1 moves zero bytes (the passthrough is local)."""
    comm = resolve_comm_dtype(comm_dtype)
    if axis_size <= 1:
        return 0
    block_size = int(block_size or DEFAULT_BLOCK_SIZE)
    n = int(num_elements)
    if comm == 'f32':
        return phases * n * 4
    padded = _padded_size(n, block_size)
    if comm == 'bf16':
        return phases * padded * 2
    return phases * (padded + (padded // block_size) * 4)       # int8


def record_collective(path, num_elements, comm_dtype, axis_size,
                      block_size=None, phases=2):
    """Count one sync call into the telemetry registry: actual bytes on
    wire at ``comm_dtype`` plus the f32-equivalent bytes the same sync
    would have moved — their ratio is the measured compression
    (tools/telemetry_report.py prints it). No-op with telemetry off."""
    if not _obs._ENABLED:
        return
    comm = resolve_comm_dtype(comm_dtype)
    _obs.inc('collective_sync_calls', 1,
             help='gradient/param sync collectives by path and comm dtype',
             path=path, dtype=comm)
    _obs.inc('collective_bytes_on_wire',
             wire_bytes(num_elements, comm, axis_size,
                        block_size=block_size, phases=phases),
             help='logical collective payload bytes at the wire dtype',
             path=path, dtype=comm)
    _obs.inc('collective_bytes_f32_equiv',
             wire_bytes(num_elements, 'f32', axis_size, phases=phases),
             help='f32-equivalent bytes for the same syncs (ratio = '
                  'compression)',
             path=path)


def quant_error_stats(x, comm_dtype=None, block_size=None):
    """Local codec round-trip error of ``x``: ``(max_abs_err,
    max_rel_err)`` where rel is against the tensor absmax. This is the
    per-stage term of the documented error contract (each of the two
    phases contributes one such round trip); call sites record it into the
    ``collective_quant_rel_error`` histogram when telemetry is on."""
    comm = resolve_comm_dtype(comm_dtype)
    x = jnp.asarray(x)
    f = jnp.ravel(x).astype(jnp.float32)
    if comm == 'f32':
        return 0.0, 0.0
    block_size = int(block_size or DEFAULT_BLOCK_SIZE)
    if comm == 'int8':
        q, s = block_quantize(f, block_size)
        rt = block_dequantize(q, s, block_size=block_size)[:f.shape[0]]
    else:
        rt = f.astype(jnp.bfloat16).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(rt - f))) if f.size else 0.0
    amax = float(jnp.max(jnp.abs(f))) if f.size else 0.0
    return err, (err / amax if amax > 0 else 0.0)


def record_quant_error(path, x, comm_dtype=None, block_size=None):
    """Observe the local round-trip relative error of one synced tensor
    (telemetry on only — costs one codec pass over ``x``)."""
    if not _obs._ENABLED:
        return
    comm = resolve_comm_dtype(comm_dtype)
    if comm == 'f32':
        return
    _, rel = quant_error_stats(x, comm, block_size)
    _obs.observe('collective_quant_rel_error', rel,
                 help='per-call codec round-trip error relative to tensor '
                      'absmax (one phase of the two-phase contract)',
                 path=path, dtype=comm)
