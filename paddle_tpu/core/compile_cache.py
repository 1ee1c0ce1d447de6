"""Persistent cross-process XLA compilation cache.

The Executor/TrainStep in-process jit caches stop re-tracing within one
process, but every new process (a bench re-run, a second fleet worker on the
same host) still recompiled every program from scratch. This module wires
jax's persistent compilation cache underneath those jit caches: compiled
executables are serialized to an on-disk directory keyed by (HLO, compile
options, jax/XLA version), so a second cold process deserializes instead of
recompiling.

Where the cache lives is decided OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets no directory. Only when jax has no directory configured does the cache
go to one fixed path inside the checkout (`DEFAULT_CACHE_DIR`, derived from
this package's location and listed in .gitignore) — fixed, because a
directory that moves from one process to the next never hits. A directory
that cannot be created is an error, not a silently cold cache.

What the key holds is kept still the same way: MLIR locations in a program
are cut to their innermost frame (``jax_include_full_tracebacks_in_locations``
off; see :func:`setup_persistent_cache`), because a pallas kernel's key
includes them and a whole traceback differs from one caller, and one
checkout path, to the next.

Environment knobs (documented in README):
- PADDLE_TPU_COMPILE_CACHE=0          disable entirely
- PADDLE_TPU_COMPILE_CACHE_MIN_COMPILE_SECS=<f>
                                      only persist compiles slower than this
                                      (default: jax's own 1.0s floor; set 0
                                      to persist everything, e.g. in tests)

Telemetry (PADDLE_TPU_TELEMETRY=1, docs/OBSERVABILITY.md): the Executor
reports its in-process program-cache lookups through record_program_cache
(compile_cache_hits / compile_cache_misses — a miss is a lower+compile), and
a jax.monitoring listener maps the persistent layer's own events onto
persistent_cache_{hits,misses} plus a compile_cache_deserialize_seconds
histogram.
"""
from __future__ import annotations

import os

from .. import observability as _obs

# <checkout>/.xla_cache — two levels above this file's package directory
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.xla_cache')

_configured = None   # None = not attempted; False = disabled; str = cache dir
_listeners_installed = False


def record_program_cache(hit):
    """Executor program+shape jit-cache lookup result (a miss means the
    program gets lowered and XLA-compiled on its first execution)."""
    if _obs._ENABLED:
        if hit:
            _obs.inc('compile_cache_hits',
                     help='in-process program+shape step-cache hits')
        else:
            _obs.inc('compile_cache_misses',
                     help='in-process step-cache misses (lower + compile)')


def _install_jax_cache_listeners():
    """Mirror jax's persistent-compilation-cache monitoring events into the
    metrics registry (the in-process counters above populate regardless)."""
    global _listeners_installed
    if _listeners_installed:
        return
    _listeners_installed = True
    from jax import monitoring

    def on_event(event, **kw):
        if not _obs._ENABLED:
            return
        if event == '/jax/compilation_cache/cache_hits':
            _obs.inc('persistent_cache_hits',
                     help='persistent XLA cache deserializations')
        elif event == '/jax/compilation_cache/cache_misses':
            _obs.inc('persistent_cache_misses',
                     help='persistent XLA cache misses (full compile)')

    def on_duration(event, duration, **kw):
        if not _obs._ENABLED:
            return
        if event == '/jax/compilation_cache/cache_retrieval_time_sec':
            _obs.observe('compile_cache_deserialize_seconds', duration,
                         help='time deserializing a persisted executable')
        elif event == '/jax/compilation_cache/compile_time_saved_sec':
            _obs.observe('compile_cache_time_saved_seconds', duration,
                         help='compile seconds avoided by a cache hit')

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def setup_persistent_cache():
    """Idempotently make sure jax has an on-disk compilation cache. Returns
    the cache dir, or None when disabled. Safe to call from every Executor /
    TrainStep constructor — only the first call does work."""
    global _configured
    _install_jax_cache_listeners()
    if _configured is not None:
        return _configured or None
    import jax
    if os.environ.get('PADDLE_TPU_COMPILE_CACHE', '1') == '0':
        # jax would still use a directory placed through its own env var
        jax.config.update('jax_enable_compilation_cache', False)
        _configured = False
        return None
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        # nobody placed the cache from outside (JAX_COMPILATION_CACHE_DIR):
        # use the one fixed in-checkout path
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update('jax_compilation_cache_dir', cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        raise RuntimeError(
            f'persistent compile cache directory {cache_dir!r} cannot be '
            f'created ({type(e).__name__}: {e}); every process would '
            'compile cold. Point JAX_COMPILATION_CACHE_DIR at a writable '
            'directory, or set PADDLE_TPU_COMPILE_CACHE=0 to run without '
            'the cache on purpose.') from e
    min_secs = os.environ.get('PADDLE_TPU_COMPILE_CACHE_MIN_COMPILE_SECS')
    if min_secs is not None:
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          float(min_secs))
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    # A key that moves never hits either. A pallas kernel's key holds its
    # Mosaic body, MLIR locations included, and by jax's default a location
    # is the whole Python traceback: the key then changes with the caller's
    # stack (tape.dispatch_op calls through another line with telemetry on)
    # and with the checkout's path. Found on the chip: the decode engine's
    # three prefill programs that hold the flash kernel compiled again, 36 s,
    # in every traced process beside their own cached executables. One
    # frame per location, the innermost, keeps the key still. jax's own
    # variable, where set, decides.
    if 'JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS' not in os.environ:
        jax.config.update('jax_include_full_tracebacks_in_locations', False)
    _configured = cache_dir
    return cache_dir
