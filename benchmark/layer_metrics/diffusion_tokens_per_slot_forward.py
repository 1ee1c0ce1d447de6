"""Answer tokens a live slot-forward of a window model yields: program
counter `decode_diffusion_tokens_committed` over the sum of
`decode_diffusion_denoise_forwards` and `decode_diffusion_commit_forwards`,
over the window. A block of B positions at `denoising_steps` d takes d + 1
forwards, so B / (d + 1) at best (4/3 at B 4, d 2), less what a prompt's
tail in the first block and a cut last block cost."""
NAME = 'diffusion_tokens_per_slot_forward'
LAYER = 'decode_engine'
UNIT = 'ratio'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    tokens = counter(run, 'decode_diffusion_tokens_committed')
    forwards = (counter(run, 'decode_diffusion_denoise_forwards') or 0) \
        + (counter(run, 'decode_diffusion_commit_forwards') or 0)
    if not tokens or not forwards:
        return None
    return tokens / forwards
