"""Time in all-reduce, all-gather, reduce-scatter and their kin over the
traced slice, on the chip where it is largest."""
NAME = 'collective_time_share'
LAYER = 'partitioner_fleet'
UNIT = '%'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    trace = run.get('trace')
    if trace is None:
        return None
    return 100.0 * max(c['collective_s'] for c in trace['chips']) \
        / trace['slice_s']
