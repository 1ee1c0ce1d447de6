"""On-chip microbench for the conv-efficiency levers (ROADMAP S5;
run on a real TPU):

  python tools/bench_fused_conv.py

Measures, slope method (dispatch-robust: the marginal time between an N-iter
and a 3N-iter run):
1. ResNet stem: plain 7×7/s2 conv vs space-to-depth 4×4/s1 re-layout.
2. Bottleneck 1×1 conv + BN + relu: XLA (conv → affine) vs the pallas
   fused-epilogue kernel.
3. Per-conv MFU of the four distinct ResNet-50 3×3 shapes (the measured
   ceiling the fused work targets).

Prints one JSON line per measurement.
"""
import functools
import json
import time

import numpy as np


def _slope_time(fn, *args, iters=(4, 16)):
    """Run iters[0] and iters[1] chained repetitions; the slope removes the
    constant dispatch/transfer overhead."""
    import jax

    def run(n):
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = fn(*args)
        jax.block_until_ready(o)
        return time.perf_counter() - t0

    run(2)  # warmup/compile
    t_small, t_big = run(iters[0]), run(iters[1])
    return (t_big - t_small) / (iters[1] - iters[0])


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.places import on_tpu as _on_tpu
    on_tpu = _on_tpu()
    print(json.dumps({"bench": "backend",
                      "backend": jax.default_backend()}), flush=True)
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32

    # --- 1. stem: plain vs s2d ---
    from paddle_tpu.ops.nn_ops import conv2d
    from paddle_tpu.ops.pallas_conv import stem_space_to_depth
    bs = 128 if on_tpu else 4
    x = jnp.asarray(rng.randn(bs, 224, 224, 3), dt)
    w = jnp.asarray(rng.randn(7, 7, 3, 64) * 0.05, dt)
    plain = jax.jit(functools.partial(conv2d, stride=2, padding=3,
                                      data_format='NHWC'))
    s2d = jax.jit(functools.partial(stem_space_to_depth,
                                    data_format='NHWC'))
    t_plain = _slope_time(plain, x, w)
    t_s2d = _slope_time(s2d, x, w)
    print(json.dumps({"bench": "stem_conv", "plain_ms": t_plain * 1e3,
                      "s2d_ms": t_s2d * 1e3,
                      "speedup": t_plain / t_s2d}), flush=True)

    # --- 2. fused 1×1 conv+bn+relu: XLA vs pallas ---
    from paddle_tpu.ops.pallas_conv import fused_conv1x1_bn_act
    for (c, o, hw) in [(256, 64, 56), (512, 128, 28), (1024, 256, 14),
                       (2048, 512, 7)]:
        xx = jnp.asarray(rng.randn(bs, hw, hw, c), dt)
        ww = jnp.asarray(rng.randn(1, 1, c, o) * 0.05, dt)
        sc = jnp.asarray(rng.rand(o) + 0.5, dt)
        sh = jnp.asarray(rng.randn(o) * 0.1, dt)
        xla = jax.jit(functools.partial(fused_conv1x1_bn_act, act='relu',
                                        force_pallas=False))
        pal = jax.jit(functools.partial(fused_conv1x1_bn_act, act='relu',
                                        force_pallas=True))
        t_xla = _slope_time(xla, xx, ww, sc, sh)
        t_pal = _slope_time(pal, xx, ww, sc, sh)
        flops = 2.0 * bs * hw * hw * c * o
        print(json.dumps({
            "bench": "conv1x1_bn_relu", "shape": f"{c}->{o}@{hw}",
            "xla_ms": t_xla * 1e3, "pallas_ms": t_pal * 1e3,
            "xla_tflops": flops / t_xla / 1e12,
            "pallas_tflops": flops / t_pal / 1e12,
            "speedup": t_xla / t_pal}), flush=True)

    # --- 3. per-conv MFU of the 3×3 ResNet shapes ---
    for (c, o, hw, s) in [(64, 64, 56, 1), (128, 128, 28, 1),
                          (256, 256, 14, 1), (512, 512, 7, 1)]:
        xx = jnp.asarray(rng.randn(bs, hw, hw, c), dt)
        ww = jnp.asarray(rng.randn(3, 3, c, o) * 0.05, dt)
        f = jax.jit(functools.partial(conv2d, stride=s, padding=1,
                                      data_format='NHWC'))
        t = _slope_time(f, xx, ww)
        flops = 2.0 * bs * hw * hw * c * o * 9 / (s * s)
        print(json.dumps({"bench": "conv3x3", "shape": f"{c}@{hw}",
                          "ms": t * 1e3,
                          "tflops": flops / t / 1e12}), flush=True)


if __name__ == '__main__':
    main()
