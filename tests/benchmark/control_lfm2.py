"""The controls behind lfm2_8b_a1b's limits (benchmark/configs/
lfm2_8b_a1b.json, `check`): the cell as served but for ONE planted fault, so
that anyone can read again what the check sees. From the root of a checkout,
on the chip (or with the tiny table, on the CPU):

    python3 tests/benchmark/control_lfm2.py <mode> --workload \
        lfm2_serve_saturated --seed N --seconds 10 --trace 0

    weights_f8      every weight the configuration states as bfloat16 (the
                    embedding's rows and the head tied to them, the conv
                    operator's two projections and its taps, the attention's
                    four, the dense feed-forward, the router, the experts'
                    three) rounded to float8_e4m3's 3 mantissa bits where it
                    is used, the nearest precision below; the reference
                    keeps them as they are. Must read `correct` false.
    state_zero      the REFERENCE with the conv state lost at the prefill's
                    end: a row at or past the prompt's length reads zero for
                    every position before it, in every conv layer: what a
                    system would read that handed a request a row of zeros
                    (or another's, cleared). Decode steps 1 and 2 read the
                    state the prefill left, step 16 does not. Must read
                    `correct` false.
    state_at_rung   the SYSTEM's prefill keeps the state of the RUNG's end,
                    (u_{rung-2}, u_{rung-1}) of the padded prompt, where the
                    request's is (u_{P-2}, u_{P-1}) of its true end. A
                    prompt that fills its rung cannot tell; the 1,025-token
                    prompt (the 2,048 rung nearly half padding) must read
                    `correct` false, by the logits of steps 1 and 2 and by
                    the state's own limit.
    taps_reversed   the REFERENCE with the filter's taps in reverse order
                    (the first tap on the newest position). Must read
                    `correct` false.
    biased_weights  the REFERENCE with the chosen experts weighed by their
                    BIASED scores s + b, where the family weighs by s. Must
                    read `correct` false.
    state_bf16      the conv state HELD in bfloat16 (rounded to 7 mantissa
                    bits after every write). Tried; whether a limit sees it
                    on the chip, where the rows it is made of are bfloat16
                    already, is written in the configuration's `check`.

A convert pair to a narrow float type and back rounds nothing as compiled
for the chip (the compiler may drop it, or widen it): the rounding is
control_brumby.py's integer arithmetic on the float32 pattern.
tests/benchmark/test_benchmark_hybrid_serve.py plants all six at the tiny
size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REFERENCE = 'benchmark_reference_lfm2_8b_a1b'
CONV_OPS = ('short_conv_prefill', 'short_conv_step')


def _wrapped(name, through):
    """Op ``name`` with ``through(fn)`` in place of its function; the entry
    of the undo list that takes it out again."""
    from paddle_tpu.ops.registry import get_op
    opdef = get_op(name)
    undo = (opdef, 'fn', opdef.fn)
    opdef.fn = through(opdef.fn)
    return undo


def _weights_f8(run):
    """control_trinity.py's roundings (`matmul`'s second operand: the conv
    operator's two projections and the attention's four; `swiglu_ffn`'s,
    `moe_router`'s, `moe_experts`'s, `lm_head`'s weights; the embedding's
    rows as `lookup_table` hands them out), and the conv ops' taps."""
    import control_trinity                     # beside this file
    from control_brumby import keep_mantissa

    def taps_f8(fn):
        def through_f8(x, w, *args, **kw):
            return fn(x, keep_mantissa(w, 3).astype(w.dtype), *args, **kw)
        return through_f8

    return control_trinity._weights_f8(run) \
        + [_wrapped(name, taps_f8) for name in CONV_OPS]


def _state_at_rung(run):
    def rung_end(fn):
        def ignoring_last(x, w, last=None):
            return fn(x, w, None)
        return ignoring_last
    return [_wrapped(CONV_OPS[0], rung_end)]


def _state_bf16(run):
    from control_brumby import keep_mantissa

    def held(fn):
        def in_bf16(*args, **kw):
            out, state = fn(*args, **kw)
            return out, keep_mantissa(state, 7)       # bfloat16's 7 bits
        return in_bf16
    return [_wrapped(name, held) for name in CONV_OPS]


def _reference_with(**patched):
    """The harness (``run``: benchmark/run.py as a module) loads
    reference/<family>.py by path, anew in every run: its loader is wrapped
    so that the module it hands out has ``patched`` in place of its own."""
    def plant(run):
        load = run._load_py

        def other(path, name):
            module = load(path, name)
            if name == REFERENCE:
                for attr, value in patched.items():
                    setattr(module, attr, value)
            return module

        run._load_py = other
        return [(run, '_load_py', load)]
    return plant


def _history_lost(u, back, prompt_len):
    import jax.numpy as jnp
    at = jnp.arange(u.shape[0])
    lost = (at >= prompt_len) & (at - back < prompt_len)
    return jnp.where(lost[:, None], 0.0,
                     jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]])


def _biased(s, biased, chosen):
    import jax.numpy as jnp
    return jnp.take_along_axis(biased, chosen, -1)


MODES = {'weights_f8': _weights_f8,
         'state_zero': _reference_with(history=_history_lost),
         'state_at_rung': _state_at_rung,
         'taps_reversed': _reference_with(
             conv_taps=lambda w: w.astype('float32')[::-1]),
         'biased_weights': _reference_with(expert_weights=_biased),
         'state_bf16': _state_bf16}


def plant(mode, run):
    """Plant the fault for runs of the harness ``run`` (benchmark/run.py as
    a module); returns the function that takes it out again. The dispatch
    keeps a jitted kernel per op and shape, so its cache is emptied on both
    sides."""
    from paddle_tpu.dygraph.tape import kernel_cache
    from paddle_tpu.ops import llm_ops  # noqa: F401  (registers the ops)
    undo = MODES[mode](run)
    kernel_cache.clear()

    def restore():
        for owner, name, value in undo:
            setattr(owner, name, value)
        kernel_cache.clear()
    return restore


if __name__ == '__main__':
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        sys.exit(f'usage: control_lfm2.py {"|".join(MODES)} <arguments of '
                 'benchmark/run.py>')
    from control_trinity import harness
    run = harness()
    plant(sys.argv[1], run)
    sys.exit(run.main(sys.argv[2:]))
