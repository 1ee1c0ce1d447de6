"""Order statistics over the samples of one run. Nothing is rounded: the
driver wants every digit, and a rounded median hides a small regression."""
from __future__ import annotations

import math


def percentile(samples, q):
    """q-th percentile (0..100), linear interpolation between closest ranks
    (numpy's default). None for no samples; +inf samples sort last, so a
    failed request (counted as +inf) drags the tail it belongs to."""
    xs = sorted(samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[lo] == xs[hi] or math.isinf(xs[lo]):
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def summary(samples, scale=1.0, qs=(50, 90, 99)):
    """{'n', 'p50', 'p90', 'p99', 'max'} of samples * scale, for [info]
    lines and out/<workload>/ files."""
    out = {'n': len(samples)}
    for q in qs:
        p = percentile(samples, q)
        out[f'p{q}'] = None if p is None else p * scale
    out['max'] = max(samples) * scale if samples else None
    return out


def spread(values):
    """Distance between the quartiles over the median: the run-to-run spread
    a bound is set from (five times the widest over the cells)."""
    med = percentile(values, 50)
    if not med:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)
