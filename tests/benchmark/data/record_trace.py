"""How tests/benchmark/data/small_trace.xplane.pb was recorded (on one v5e
chip, through the chip tool): three runs of a small jitted program (a matmul,
an elementwise pass, a reduction) with the host asleep between them, under
jax.profiler with this benchmark's begin and end marks.

    python3 tests/benchmark/data/record_trace.py <out.xplane.pb> [hlo_proto 0|1]
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..', '..',
                                'benchmark', 'lib'))
import xplane  # noqa: E402


def mark(label):
    with jax.profiler.TraceAnnotation(
            xplane.mark_name(label, time.perf_counter_ns())):
        pass


def main(out, hlo_proto):
    @jax.jit
    def program(x):
        y = jnp.tanh(x @ x)
        return y, y.sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(program(x))
    directory = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = bool(hlo_proto)
    jax.profiler.start_trace(directory, profiler_options=options)
    mark('begin')
    for _ in range(3):
        jax.block_until_ready(program(x))
        time.sleep(0.002)
    mark('end')
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(directory, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))[0]
    shutil.copy(found, out)
    shutil.rmtree(directory)
    print(out, os.path.getsize(out), 'bytes')
    print(xplane.reduce(out))


if __name__ == '__main__':
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0)
