"""Peak device memory on the fullest chip, in GB (1e9 bytes), from
device.memory_stats() as the harness samples it with work in flight: the
larger of bytes_in_use + bytes_reserved (buffers, plus the runtime's
reservation for the loaded program's temporaries) and the runtime's own
peak_bytes_in_use, which sees buffers only. A counter of the runtime's
allocator, read by the harness and not exported by the program:
"program_counter" is the nearest of the four source labels. Moves the
end-to-end metric through the batch or the slots that fit; guards donation
and pool sizing."""
NAME = 'train_peak_hbm_gb'
LAYER = 'device'
UNIT = 'GB'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    return ctx.module('lib', 'readers').peak_hbm_gb(run)
