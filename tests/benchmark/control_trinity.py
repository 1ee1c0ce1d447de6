"""The controls behind trinity_large_preview's limits (benchmark/configs/
trinity_large_preview.json, `check`): the cell as served but for ONE planted
fault, so that anyone can read again what the check sees. From the root of a
checkout, on the chip (or with the tiny table, on the CPU):

    python3 tests/benchmark/control_trinity.py <mode> --workload \
        trinity_serve_saturated --seed N --seconds 10 --trace 0

    weights_f8       every weight matrix the configuration states as
                     bfloat16 (the embedding's rows, the attention's five
                     projections, the dense and the shared feed-forward, the
                     router, the held experts' three, the head) rounded to
                     float8_e4m3's 3 mantissa bits where it is used, the
                     nearest precision below; the reference keeps them as
                     they are. Must read `correct` false.
    full_reference   the REFERENCE with every layer full (the window
                     ignored) where the system's sliding layers see 4,096
                     positions: what a system would read that kept every
                     position of every layer. A prompt inside the window
                     cannot tell; the prompts past it must read `correct`
                     false.
    rope_everywhere  the REFERENCE with rotary positions on the full layer
                     too, where the system's full layer has no position
                     encoding at all. Must read `correct` false.
    span_short       the SYSTEM's span one block short (4,080 where the
                     reference keeps 4,096): the ring one block shorter, the
                     mask's edge 16 positions early. Tried; whether the
                     limit sees it is written in the configuration's `check`
                     and PERF.md section 6.

A convert pair to a narrow float type and back rounds nothing as compiled
for the chip (the compiler may drop it, or widen it): the rounding is
control_brumby.py's integer arithmetic on the float32 pattern.
tests/benchmark/test_benchmark_sliding_serve.py plants all four at the tiny
size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REFERENCE = 'benchmark_reference_trinity_large_preview'


def _weights_f8(run):
    """Through the ops that take them: `matmul`'s second operand (the
    attention's five projections), `swiglu_ffn`'s three (the dense and the
    shared feed-forward), `moe_router`'s, `moe_experts`'s three, `lm_head`'s,
    and the embedding's rows as `lookup_table` hands them out (rounding the
    rows taken is rounding the table)."""
    from control_brumby import keep_mantissa    # beside this file
    from paddle_tpu.ops.registry import get_op
    undo = []

    def m3(w):
        return keep_mantissa(w, 3).astype(w.dtype)   # e4m3's 3 bits

    def wrap(name, weights=(), out=False):
        opdef = get_op(name)

        def through_f8(*args, _fn=opdef.fn, **kw):
            args = [m3(a) if i in weights else a for i, a in enumerate(args)]
            kw = {k: m3(v) if k in weights else v for k, v in kw.items()}
            got = _fn(*args, **kw)
            return m3(got) if out else got

        undo.append((opdef, 'fn', opdef.fn))
        opdef.fn = through_f8

    wrap('matmul', weights=(1, 'y'))
    wrap('swiglu_ffn', weights=(1, 2, 3, 'w_gate', 'w_up', 'w_down'))
    wrap('moe_router', weights=(1, 'w_gate'))
    wrap('moe_experts', weights=(3, 4, 5, 'w_gate', 'w_up', 'w_down'))
    wrap('lm_head', weights=(1, 'w'))
    wrap('lookup_table', out=True)
    return undo


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


def _span_short(run):
    from paddle_tpu.models.sliding_moe_lm import SlidingMoEConfig
    span = SlidingMoEConfig.span

    def shorter(self, layer):
        whole = span(self, layer)
        return whole - SPAN_SHORT_BY if whole else 0

    SlidingMoEConfig.span = shorter
    return [(SlidingMoEConfig, 'span', span)]


# positions the control `span_short` takes off the system's span: a block of
# the cell's traffic (the tiny table's blocks are 4: the test sets it)
SPAN_SHORT_BY = 16

MODES = {'weights_f8': _weights_f8,
         'full_reference': _reference_with(layer_span=lambda m, i: 0),
         'rope_everywhere': _reference_with(layer_rotary=lambda m, i: True),
         'span_short': _span_short}


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


def harness():
    """benchmark/run.py, loaded by path (benchmark/ is not a package)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'bench_run', os.path.join(ROOT, 'benchmark', 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


if __name__ == '__main__':
    sys.path.insert(0, ROOT)
    if len(sys.argv) < 2 or sys.argv[1] not in MODES:
        sys.exit(f'usage: control_trinity.py {"|".join(MODES)} <arguments '
                 'of benchmark/run.py>')
    run = harness()
    plant(sys.argv[1], run)
    sys.exit(run.main(sys.argv[2:]))
