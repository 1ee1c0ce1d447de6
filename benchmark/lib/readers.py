"""Arithmetic shared by the per-layer readers that exist once for the
trainer and once for the decode server (a per-layer metric names the one
end-to-end metric it moves, and the two products have different ones)."""


def device_idle_share(run):
    """1 - (union of device-op intervals, mean over chips) / traced slice."""
    trace = run.get('trace')
    return None if trace is None else 100.0 * trace['idle_share']


def peak_hbm_gb(run):
    peak = run['device']['memory_peak_bytes']
    return peak / 1e9 if peak else None


def mxu_time_share(run, categories):
    """Share of the device's busy op time in convolution and dot fusions, by
    the hlo_category the trace carries for each op."""
    trace = run.get('trace')
    if trace is None:
        return None
    mxu = all_ops = 0.0
    for chip in trace['chips']:
        for category, seconds in chip['categories'].items():
            all_ops += seconds
            if category in categories:
                mxu += seconds
    return 100.0 * mxu / all_ops if all_ops else None


def compiles_in_window(run):
    return float(run['compiles']['window']['compiles'])


def histogram(run, name):
    """(sum, count, recent samples) of a program histogram, over all of its
    label sets; None where the program recorded none."""
    metric = (run.get('registry') or {}).get(name)
    if not metric or not metric['samples']:
        return None
    samples = metric['samples']
    return (sum(s['sum'] for s in samples), sum(s['count'] for s in samples),
            [x for s in samples for x in s['recent']])
