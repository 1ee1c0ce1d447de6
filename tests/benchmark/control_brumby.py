"""The controls behind brumby_14b's limits (benchmark/configs/brumby_14b.json,
`check`): the program as served but for ONE planted fault of precision, so
that anyone can read again what each limit sees and what it does not. From
the root of a checkout, on the chip (or with the tiny table, on the CPU):

    python3 tests/benchmark/control_brumby.py <mode> --workload \
        brumby_serve_saturated --seed N --seconds 10 --trace 0

    state_bf16      the recurrent state HELD in bfloat16: rounded to 7
                    mantissa bits after every write, a prefill's final state
                    and every step's update. Must read `correct` false, by
                    `state_tolerance` (the logits do not see it).
    ffn_f8          the feed-forward's three weights rounded to
                    float8_e4m3's 3 mantissa bits where they are used (the
                    nearest precision below the bf16 stated for the
                    weights); the reference keeps them as they are. Must
                    read `correct` false, by `logit_tolerance`.
    state_one_pass  every contraction with the float32 state (a prefill's
                    φ(k)ᵀ [v, 1] into the state, a step's φ(q)ᵀ S) at ONE
                    bf16 pass where `dtype_policy` says "highest". Reads
                    `correct` TRUE: no limit of the check holds that
                    statement (PERF.md section 6, PR 30).

A convert pair to a narrow float type and back rounds nothing as compiled
for the chip (the compiler may drop it, or widen it): the roundings here
are integer arithmetic on the float32 pattern.
tests/benchmark/test_benchmark_retention_serve.py plants `state_bf16` at the
tiny size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def keep_mantissa(x, bits):
    """x rounded (half up) to ``bits`` explicit mantissa bits, in float32."""
    import jax
    import jax.numpy as jnp
    drop = 23 - bits
    pattern = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    pattern = (pattern + jnp.uint32(1 << (drop - 1))) \
        & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(pattern, jnp.float32)


def _state_bf16():
    from paddle_tpu.ops.registry import get_op
    undo = []
    for name in ('power_retention_prefill', 'power_retention_step'):
        opdef = get_op(name)

        def held(*args, _fn=opdef.fn, **kw):
            out, state = _fn(*args, **kw)
            return out, keep_mantissa(state, 7)       # bfloat16's 7 bits

        undo.append((opdef, 'fn', opdef.fn))
        opdef.fn = held
    return undo


def _ffn_f8():
    from paddle_tpu.ops.registry import get_op
    opdef = get_op('swiglu_ffn')

    def through_f8(x, w_gate, w_up, w_down, _fn=opdef.fn):
        m3 = lambda w: keep_mantissa(w, 3).astype(w.dtype)   # e4m3's 3 bits
        return _fn(x, m3(w_gate), m3(w_up), m3(w_down))

    undo = [(opdef, 'fn', opdef.fn)]
    opdef.fn = through_f8
    return undo


def _state_one_pass():
    from jax import lax
    from paddle_tpu.ops import llm_ops
    undo = [(llm_ops, '_STATE_PRECISION', llm_ops._STATE_PRECISION)]
    llm_ops._STATE_PRECISION = lax.Precision.DEFAULT
    return undo


MODES = {'state_bf16': _state_bf16, 'ffn_f8': _ffn_f8,
         'state_one_pass': _state_one_pass}


def plant(mode):
    """Plant the fault; returns the function that takes it out again. The
    dispatch keeps a jitted kernel per op and shape, so its cache is
    emptied on both sides."""
    from paddle_tpu.dygraph.tape import kernel_cache
    from paddle_tpu.ops import llm_ops  # noqa: F401  (registers the ops)
    undo = MODES[mode]()
    kernel_cache.clear()

    def restore():
        for owner, name, value in undo:
            setattr(owner, name, value)
        kernel_cache.clear()
    return restore


if __name__ == '__main__':
    import importlib.util
    sys.path.insert(0, ROOT)
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        sys.exit(f'usage: control_brumby.py {"|".join(MODES)} <arguments '
                 'of benchmark/run.py>')
    plant(sys.argv[1])
    spec = importlib.util.spec_from_file_location(
        'bench_run', os.path.join(ROOT, 'benchmark', 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    sys.exit(run.main(sys.argv[2:]))
