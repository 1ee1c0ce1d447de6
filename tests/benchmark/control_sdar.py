"""The controls behind sdar_30b_a3b's limits (benchmark/configs/
sdar_30b_a3b.json, `check`): the cell as served but for ONE planted fault,
so that anyone can read again what the check sees. From the root of a
checkout, on the chip (or with the tiny table, on the CPU):

    python3 tests/benchmark/control_sdar.py <mode> --workload \
        sdar_serve_saturated --seed N --seconds 10 --trace 0

    weights_f8        every weight matrix the configuration states as
                      bfloat16 (the embedding's rows, the attention's four
                      projections, the router, the experts' three, the head)
                      rounded to float8_e4m3's 3 mantissa bits where it is
                      used, the nearest precision below; the reference keeps
                      them as they are. Must read `correct` false, at both
                      checked forwards.
    experts_f8        the routed experts' three weights alone so rounded.
                      Reads `correct` TRUE on the chip: eight experts a
                      token average their independent roundings, and what is
                      left (0.011-0.016 of a row's largest logit) lies
                      inside what bf16 activations and the router's
                      near-ties leave on a sound run (0.006-0.019; PERF.md
                      section 6, PR 32). Kept so that anyone can read it
                      again; at float32 (the tiny table) it fails.
    causal_reference  the REFERENCE under a plain causal mask (key j visible
                      to row i iff j <= i) where the system attends under
                      the block mask: what a system would read that served
                      this model causally. Must read `correct` false.
    skip_commit       a commit forward feeds what the block's LAST DENOISING
                      forward fed (`MASK` ids where positions were still
                      masked) and not the finished block: the cache keeps
                      the K/V of `MASK` inputs, as it would if commits were
                      skipped and a denoising forward's writes kept. The
                      first checked forward (over the prefill's K/V alone)
                      stays sound; the later one, over 16 such blocks, must
                      read `correct` false.

A convert pair to a narrow float type and back rounds nothing as compiled
for the chip (the compiler may drop it, or widen it): the rounding is
control_brumby.py's integer arithmetic on the float32 pattern.
tests/benchmark/test_benchmark_diffusion_serve.py plants all four at the
tiny size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _experts_f8(run):
    from control_brumby import keep_mantissa    # beside this file
    from paddle_tpu.ops.registry import get_op
    opdef = get_op('moe_experts')

    def through_f8(x, ids, weights, w_gate, w_up, w_down, _fn=opdef.fn):
        m3 = lambda w: keep_mantissa(w, 3).astype(w.dtype)   # e4m3's 3 bits
        return _fn(x, ids, weights, m3(w_gate), m3(w_up), m3(w_down))

    undo = [(opdef, 'fn', opdef.fn)]
    opdef.fn = through_f8
    return undo


def _weights_f8(run):
    """`_experts_f8` and, through the ops that take them, every other
    bfloat16 weight matrix: `matmul`'s second operand (the attention's
    projections and the router, the only `matmul`s of this model),
    `lm_head`'s, `moe_router`'s, and the embedding's rows as `lookup_table`
    hands them out (rounding the rows taken is rounding the table)."""
    from control_brumby import keep_mantissa
    from paddle_tpu.ops.registry import get_op
    undo = _experts_f8(run)

    def m3(w):
        return keep_mantissa(w, 3).astype(w.dtype)

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
    wrap('lm_head', weights=(1, 'w'))
    wrap('moe_router', weights=(1, 'w_gate'))
    wrap('lookup_table', out=True)
    return undo


def _causal_reference(run):
    """The harness (``run``: benchmark/run.py as a module) loads
    reference/<family>.py by path, anew in every run: its loader is wrapped
    so that the module it hands out sees causally."""
    load = run._load_py

    def causal(path, name):
        module = load(path, name)
        if name == 'benchmark_reference_sdar_30b_a3b':
            module.visible = lambda pos, block_length: \
                pos[None, :] <= pos[:, None]
        return module

    run._load_py = causal
    return [(run, '_load_py', load)]


def _skip_commit(run):
    from paddle_tpu.serving.decode.engine import DecodeEngine
    step = DecodeEngine.window_step

    def stale_commit(self, blocks, masked, quota, tables, commits,
                     return_rows=False):
        fed = getattr(self, '_control_fed', None)
        if fed is None:
            fed = self._control_fed = blocks.copy()
        stale = [bool(t is not None and c) for t, c in zip(tables, commits)]
        kept = blocks[stale].copy()
        blocks[stale] = fed[stale]          # what the last denoising fed
        live = [t is not None and not c for t, c in zip(tables, commits)]
        fed[live] = blocks[live]
        try:
            return step(self, blocks, masked, quota, tables, commits,
                        return_rows)
        finally:
            blocks[stale] = kept            # the host still emits its tokens

    DecodeEngine.window_step = stale_commit
    return [(DecodeEngine, 'window_step', step)]


MODES = {'weights_f8': _weights_f8, 'experts_f8': _experts_f8,
         'causal_reference': _causal_reference, 'skip_commit': _skip_commit}


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
        sys.exit(f'usage: control_sdar.py {"|".join(MODES)} <arguments of '
                 'benchmark/run.py>')
    run = harness()
    plant(sys.argv[1], run)
    sys.exit(run.main(sys.argv[2:]))
