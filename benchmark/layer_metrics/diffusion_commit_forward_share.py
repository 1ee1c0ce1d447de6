"""Share of a window model's live slot-forwards that commit a block
(program counter `decode_diffusion_commit_forwards` over that plus
`decode_diffusion_denoise_forwards`, over the window): forwards that feed a
finished block once more to keep its K/V and whose picks nobody reads. 1 in
d + 1 at `denoising_steps` d (33% at 2): what a commit fused into the next
block's first forward would take away."""
NAME = 'diffusion_commit_forward_share'
LAYER = 'decode_engine'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    commits = counter(run, 'decode_diffusion_commit_forwards')
    denoise = counter(run, 'decode_diffusion_denoise_forwards')
    if not commits or denoise is None:
        return None
    return 100.0 * commits / (commits + denoise)
