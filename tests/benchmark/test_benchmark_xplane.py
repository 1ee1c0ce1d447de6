"""benchmark/lib/xplane.py: the interval arithmetic on hand-made intervals,
and the reduction on a small trace recorded on a TPU v5e
(data/small_trace.xplane.pb; data/record_trace.py says how): three runs of a
2048x2048 bf16 matmul+tanh+sum, ~91 us each, the host asleep 2 ms between."""
import os

import pytest

from bench_testlib import DATA, load

xplane = load('lib/xplane.py')
TRACE = os.path.join(DATA, 'small_trace.xplane.pb')


def test_union_merges_and_drops_empty_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    # nested and duplicated events are not counted twice
    assert xplane.total(xplane.union([(0, 10), (2, 3), (2, 3), (8, 12)])) == 12


def test_subtract_and_gaps():
    a = [(0, 10), (20, 30)]
    assert xplane.subtract(a, []) == a
    assert xplane.subtract(a, [(0, 30)]) == []
    assert xplane.subtract(a, [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert xplane.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert xplane.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == [(3, 5), (8, 10)]


def test_exposed_collective_time_is_what_compute_does_not_cover():
    # an all-reduce in flight from 10 to 50, compute ops at 0-30 and 40-45:
    # 30-40 and 45-50 are exposed
    collective = xplane.union([(10, 50)])
    compute = xplane.union([(0, 30), (40, 45)])
    exposed = xplane.subtract(collective, compute)
    assert exposed == [(30, 40), (45, 50)]
    assert xplane.total(exposed) == 15
    busy = xplane.union(compute + collective)
    assert xplane.total(busy) == 50 and xplane.gaps(busy, 0, 60) == [(50, 60)]


def test_collectives_are_known_by_category_or_opcode():
    text = ('%all-reduce-start.3 = (f32[768,768]{1,0}, f32[768,768]{1,0}) '
            'all-reduce-start(f32[768,768]{1,0} %p), replica_groups={}')
    assert xplane.is_collective(text)
    assert xplane.is_collective('%fusion.1 = f32[8] fusion(f32[8] %x)',
                                'all-reduce')
    assert not xplane.is_collective('%fusion.1 = f32[8] fusion(f32[8] %x)',
                                    'loop fusion')
    # an operand named after a collective does not make the op one
    assert not xplane.is_collective(
        '%add.1 = f32[8]{0} add(f32[8]{0} %all-reduce-done.3, f32[8]{0} %y)')


def test_signature_drops_the_instance_and_the_layouts():
    a = xplane.signature(
        '%fusion.408 = (bf16[128,128,3072]{2,1,0:T(8,128)(2,1)}, bf16[128,128'
        ',3072]{2,1,0:T(8,128)(2,1)}) fusion(bf16[3072]{0} %c), kind=kOutput',
        'convolution fusion')
    b = xplane.signature(
        '%fusion.402 = (bf16[128,128,3072]{2,1,0:T(8,128)(2,1)}, bf16[128,128'
        ',3072]{2,1,0:T(8,128)(2,1)}) fusion(bf16[3072]{0} %d), kind=kOutput',
        'convolution fusion')
    assert a == b == ('convolution fusion (bf16[128,128,3072], '
                      'bf16[128,128,3072])')
    assert xplane.signature('%copy.1 = f32[12,4104,16,64]{3,2,1,0} copy('
                            'f32[12,4104,16,64]{1,3,2,0} %pages.1)', None) \
        == 'copy f32[12,4104,16,64]'


def test_recorded_trace_reduces_to_what_was_run():
    trace = xplane.reduce(TRACE)
    assert len(trace['chips']) == 1
    chip = trace['chips'][0]
    assert chip['plane'] == '/device:TPU:0'
    # the slice runs between the marks: 11.9 ms on the host's clock
    assert trace['slice_s'] == pytest.approx(0.011913909, rel=1e-6)
    # the device's clock runs ~0.8 ms ahead of the host's here, so the first
    # of the three programs falls before the begin mark: two are counted,
    # 3 ops each, 91.24 us of matmul fusion each
    assert chip['op_count'] == 6
    assert chip['busy_s'] == pytest.approx(182.52e-6, rel=1e-3)
    assert trace['busy_s'] == chip['busy_s']
    assert trace['idle_share'] == pytest.approx(0.98468, abs=1e-5)
    top = chip['ops'][0]
    assert top[0] == 'convolution fusion (bf16[], bf16[2048,2048])'
    assert top[2] == 2 and top[3] == '%fusion'
    assert top[1] == pytest.approx(182.49e-6, rel=1e-3)
    # the compiler's category rides on the event's metadata
    assert set(chip['categories']) == {'convolution fusion', 'copy-start',
                                       'copy-done'}
    assert chip['collective_s'] == 0.0
    # the two long gaps are the host's 2 ms sleeps (+ dispatch), ~4.1 ms
    long_gaps = [hi - lo for lo, hi in chip['gaps'][:2]]
    assert all(3.9e6 < g < 4.3e6 for g in long_gaps)
    # the marks carry perf_counter_ns: one offset puts both clocks together
    assert trace['offset_ns'] == pytest.approx(-31337685132.0, abs=2e3)


def test_gaps_go_to_the_host_span_that_covers_them():
    trace = xplane.reduce(TRACE)
    off = trace['offset_ns']
    (a0, b0), (a1, b1) = sorted(trace['chips'][0]['gaps'][:2])
    spans = [('outer', a0 - off - 10, b1 - off + 10),     # covers both
             ('inner', a1 - off - 10, b1 - off + 10)]     # covers the second
    named = dict(xplane.attribute_gaps([(a0, b0), (a1, b1)], spans, off,
                                       ['inner', 'outer']))
    assert named['outer'] == pytest.approx((b0 - a0) * 1e-9)
    assert named['inner'] == pytest.approx((b1 - a1) * 1e-9)
    nobody = xplane.attribute_gaps([(a0, b0)], [], off, ['inner'])
    assert nobody == [['no span', pytest.approx((b0 - a0) * 1e-9)]]


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    # what a CPU rehearsal records: readers then leave device metrics out
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile', '*',
                                  '*.xplane.pb'))[0]
    assert xplane.reduce(path) is None
