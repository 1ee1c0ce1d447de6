"""Roofline share of the grouped expert feed-forward: the least time the
chip could take for the work of the traced slice's engine calls
(flops/<family>.py::experts over the assignments they computed and the
experts they gave a row: each such expert's weights read once) over the
device seconds of the ops under `moe/experts` in that slice
(lib/scoped_ops.py). Decode steps are bound by the experts' bytes, prefills
by their FLOPs; the max of the two is taken over the slice's sum."""
NAME = 'moe_experts_roofline'
LAYER = 'ops_kernels'
UNIT = '%'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    scoped = ctx.module('lib', 'scoped_ops')
    found = scoped.reduce(run, ctx)
    if not found:
        return None
    flops, nbytes = ctx.module('flops', ctx.config['family']).experts(
        ctx.config, found['work']['expert_assignments'],
        found['work']['experts_touched'])
    return scoped.roofline_share(run, ctx, 'moe/experts', flops, nbytes)
