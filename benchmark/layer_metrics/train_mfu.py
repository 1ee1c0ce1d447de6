"""Model FLOP/s utilisation: analytic FLOPs per sample (flops/<family>.py,
forward + backward, recomputation not counted) x samples per second over
the window, over chips x the chip's published bf16 peak (lib/peaks.json).
An end-to-end utilisation, not a kernel's roofline share."""
NAME = 'train_mfu'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'train_samples_per_s'
RUNNERS = ('train_step',)


def read(run, ctx):
    if not run['peaks']:
        return None
    rate = run['counts']['samples'] / run['window_s']
    return 100.0 * run['flops_per_sample'] * rate / (
        run['counts']['chips'] * run['peaks']['bf16_flops_per_s'])
