"""The controls behind nemotron3_super_120b_a12b's limits (benchmark/configs/
nemotron3_super_120b_a12b.json, `check`): the cell as served but for ONE
planted fault, so that anyone can read again what the check sees. From the
root of a checkout, on the chip (or with the tiny table, on the CPU):

    python3 tests/benchmark/control_nemotron3.py <mode> --workload \
        nemotron3_serve_saturated --seed N --seconds 10 --trace 0

    weights_f8      every weight the configuration states as bfloat16 (the
                    embedding's rows, the head, every projection, the
                    filter's taps and bias, the gated norm's weight, the
                    router, the experts' two, the shared expert's two)
                    rounded to float8_e4m3's 3 mantissa bits where it is
                    used, the nearest precision below; the reference keeps
                    them as they are. Must read `correct` false.
    state_bf16      the Mamba state HELD in bfloat16: S and the filter's
                    window rounded to 7 mantissa bits after every write (the
                    prefill's and each step's), the nearest precision below
                    the stated float32. Must read `correct` false.
    padding_enters  the SYSTEM's prefill lets a rung's padding rows enter S
                    (Δ left as the rows give it, where the family's rows
                    past the prompt must leave S as it stood); the window is
                    still taken at the prompt's true end. A prompt that
                    fills its rung cannot tell; the others must read
                    `correct` false.
    norm_whole      the REFERENCE with the gated norm over all 8,192 lanes
                    at once, where the family norms groups of 1,024. Must
                    read `correct` false.
    relu            the REFERENCE with relu for the experts' relu². Must
                    read `correct` false.
    biased_weights  the REFERENCE with the chosen experts weighed by their
                    BIASED scores s + b, where the family weighs by s. Must
                    read `correct` false.

A convert pair to a narrow float type and back rounds nothing as compiled
for the chip (the compiler may drop it, or widen it): the rounding is
control_brumby.py's integer arithmetic on the float32 pattern.
tests/benchmark/test_benchmark_ssm_serve.py plants all six at the tiny size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REFERENCE = 'benchmark_reference_nemotron_h'
SSM_OPS = ('ssm_scan_prefill', 'ssm_scan_step')


def _wrapped(name, through):
    """Op ``name`` with ``through(fn)`` in place of its function; the entry
    of the undo list that takes it out again."""
    from paddle_tpu.ops.registry import get_op
    opdef = get_op(name)
    undo = (opdef, 'fn', opdef.fn)
    opdef.fn = through(opdef.fn)
    return undo


def _rounding(weights, out=False):
    """An op's function with its arguments at ``weights`` (positions and
    names) rounded to e4m3's 3 mantissa bits, and its result too where
    ``out``; None arguments (a non-gated expert's gate) pass."""
    from control_brumby import keep_mantissa

    def m3(w):
        return w if w is None else keep_mantissa(w, 3).astype(w.dtype)

    def through(fn):
        def through_f8(*args, **kw):
            args = [m3(a) if i in weights else a for i, a in enumerate(args)]
            kw = {k: m3(v) if k in weights else v for k, v in kw.items()}
            got = fn(*args, **kw)
            return m3(got) if out else got
        return through_f8
    return through


def _weights_f8(run):
    return [_wrapped(name, _rounding(weights, out)) for name, weights, out in (
        ('matmul', (1, 'y'), False),
        ('relu2_ffn', (1, 2, 'w_up', 'w_down'), False),
        ('moe_router', (1, 'w_gate'), False),
        ('moe_experts', (4, 5, 'w_up', 'w_down'), False),
        ('lm_head', (1, 'w'), False),
        ('lookup_table', (), True))] + [
        _wrapped(name, _rounding((1, 2, 3, 7, 'w_in', 'conv_w', 'conv_b',
                                  'norm_w')))
        for name in SSM_OPS]


def _state_bf16(run):
    from control_brumby import keep_mantissa

    def held(fn):
        def in_bf16(*args, **kw):
            out, state = fn(*args, **kw)
            return out, keep_mantissa(state, 7)       # bfloat16's 7 bits
        return in_bf16
    return [_wrapped(name, held) for name in SSM_OPS]


def _padding_enters(run):
    def entering(fn):
        def padding_in(*args, **kw):
            import jax.numpy as jnp
            out, kept = fn(*args, **kw)
            # `last`, the op's ninth input, left out: every row enters
            _, rung = fn(*args[:8], None, *args[9:], **kw)
            # S of the rung's end beside the window of the prompt's: the
            # block's first P·N rows are S
            cells = kw['head_dim'] * (
                (args[2].shape[1] - args[4].shape[0] * kw['head_dim'])
                // (2 * kw['groups']))
            return out, jnp.concatenate(
                [rung[..., :cells, :], kept[..., cells:, :]], -2)
        return padding_in
    return [_wrapped(SSM_OPS[0], entering)]


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


def _norm_whole(y, z, weight, m):
    import jax
    import jax.numpy as jnp
    g = y * jax.nn.silu(z)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                        + m['layer_norm_epsilon'])
    return g * inv * weight.astype(jnp.float32)


def _relu(u):
    import jax
    return jax.nn.relu(u)


def _biased(s, biased, chosen):
    import jax.numpy as jnp
    return jnp.take_along_axis(biased, chosen, -1)


MODES = {'weights_f8': _weights_f8,
         'state_bf16': _state_bf16,
         'padding_enters': _padding_enters,
         'norm_whole': _reference_with(gated_norm=_norm_whole),
         'relu': _reference_with(relu2=_relu),
         'biased_weights': _reference_with(expert_weights=_biased)}


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
        sys.exit(f'usage: control_nemotron3.py {"|".join(MODES)} <arguments '
                 'of benchmark/run.py>')
    from control_trinity import harness
    run = harness()
    plant(sys.argv[1], run)
    sys.exit(run.main(sys.argv[2:]))
