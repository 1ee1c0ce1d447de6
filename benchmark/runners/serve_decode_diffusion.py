"""Runner `serve_decode_diffusion`: `serve_decode`, whole, for a WINDOW model
that generates by block diffusion (a step feeds B rows a slot and yields 0
or up to B tokens). Only the logit check differs.

What is new in such a model is not a layer but the mask and the step: rows
that see their whole block, K/V that a denoising forward writes and only a
commit forward keeps, a block that opens with a prompt's tail beside masks.
So per prompt (the shortest and the longest the traffic allows, and draws
from its distribution between) the check drives the engine as the scheduler
does, through `engine.window_step` and the program's own schedule, and
holds TWO forwards, all B rows of each, to the reference's whole-sequence
forward under the block mask (reference/<family>.py, float32 at precision
"highest") over the system's own tokens:

  first   the first denoising forward of the first block: the prompt's last
          P mod B tokens beside `MASK` ids, over the K/V the prefill wrote;
  later   the SECOND denoising forward of the block after `check_blocks`
          committed ones (a key of the traffic file): a partly unmasked
          block over K/V that commit forwards wrote. The blocks between are
          the system's own denoising and commit forwards, as served.

Per forward the error is the largest over its B rows of max |system −
reference| ÷ max |reference| of the row. A cache that keeps the K/V of a
denoising forward (of `MASK` inputs), a causal mask, a staircase extent, a
block that straddles cache blocks, or weights of lower precision are far
outside the tolerance at `later`, `first` or both
(tests/benchmark/control_sdar.py plants them).

The routed experts make the comparison the routed check's
(runners/serve_decode_routed.py): the engine reports which experts made the
rows it scored (`engine.last_stats['expert_ids']`), and the reference
follows such a choice at the checked positions only where its OWN softmax
probabilities call it a near-tie (the configuration's `tie_margin`);
elsewhere its own choice stands and the row is far from the system's.

Everything else (set-up, warm-up, load, window, the other checks, what is
returned) is runners/serve_decode.py::run, unedited, and the run says
'runner': 'serve_decode' so that every reader of that runner applies.
"""
from __future__ import annotations

import numpy as np


def _row_error(got, want):
    """Largest over the rows of max |got − want| ÷ max |want| of the row."""
    return float(max(np.abs(g - w).max() / np.abs(w).max()
                     for g, w in zip(got, want)))


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the first block's first denoising forward,
    error of the second denoising forward after `check_blocks` commits], as
    serve_decode's entries."""
    import jax
    from paddle_tpu.serving.decode.diffusion import denoise_quota
    loadgen = ctx.module('lib', 'loadgen')
    load, engine_spec = ctx.traffic['load'], ctx.traffic['engine']
    steps = engine_spec['denoising_steps']
    committed = ctx.traffic['check_blocks']
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    n = ctx.traffic['check_prompts']
    lens = [load['prompt_len']['min']] \
        + [loadgen.quantile_len(load['prompt_len'], rng.random())
           for _ in range(n - 2)] \
        + [load['prompt_len']['max']]
    slots, b = engine.slots, engine.window
    mask_id = engine.model.mask_token_id
    size = engine.block_size
    pad = -(-(load['prompt_len']['max'] + (committed + 1) * b) // size) * size
    rows = reference.make_rows(ctx.config, pad)
    tie_margin = ctx.config['check']['tie_margin']
    blocks = np.zeros((slots, b), np.int64)
    masked = np.zeros((slots, b), bool)
    quota = np.zeros(slots, np.int64)
    errors = []
    for plen in lens:
        prompt = rng.integers(1, load['vocab'], plen).tolist()
        table = engine.reserve_table(plen, (committed + 2) * b)
        tables = [table] + [None] * (slots - 1)
        engine.prefill(prompt, table)
        whole = table.context_len
        sequence, fixed = prompt[:whole], prompt[whole:]
        held = []           # (tokens fed, system rows (B, V), experts)

        def forward(keep=False):
            """One forward of slot 0's block; with ``keep`` its rows and
            the experts behind them are held for the reference."""
            commit = not masked[0].any()
            fed = sequence + blocks[0].tolist()
            out = engine.window_step(blocks, masked, quota, tables,
                                     [commit] + [False] * (slots - 1),
                                     return_rows=keep)
            if keep:
                chosen = np.asarray(engine.last_stats['expert_ids'])
                held.append((fed, np.array(out[2][0]), chosen[:, :b]))
            return commit

        def open_block(fixed=()):
            blocks[0, :len(fixed)] = fixed
            blocks[0, len(fixed):] = mask_id
            masked[0] = np.arange(b) >= len(fixed)
            quota[0] = denoise_quota(b - len(fixed), steps)

        open_block(fixed)
        forward(keep=True)                      # `first`
        for done in range(committed):
            while not forward():
                pass
            sequence = sequence + blocks[0].tolist()
            open_block()
        forward()
        if masked[0].any():
            forward(keep=True)                  # `later`
        else:       # denoising_steps 1: the block's one denoising forward
            held.append(held[-1])
        engine.release_table(table)
        entry, gaps = [plen], []
        for fed, got, chosen in held:
            at = list(range(len(fed) - b, len(fed)))
            # "highest" for the reference alone: the engine's calls above
            # must run as they are served
            with jax.default_matmul_precision('highest'):
                want, gap = rows(params, fed, at,
                                 {p: chosen[:, i] for i, p in enumerate(at)},
                                 tie_margin)
            entry.append(_row_error(got, np.asarray(want)))
            gaps.append(np.asarray(gap).max(0))
        errors.append(entry)
        # every prompt's line: the gaps are what `tie_margin` is set from (a
        # gap above zero is a choice the reference would have made the other
        # way; above `tie_margin` it was not followed)
        ctx.info(f'logit check, prompt {plen} ({len(fixed)} of its tokens '
                 f'open the first block): errors of the first forward and '
                 f'of the second denoising forward after {committed} '
                 f'commits {[round(e, 5) for e in entry[1:]]}; widest gap '
                 f"of the system's choices by layer, first "
                 f'{[float(f"{g:.3g}") for g in gaps[0]]}, later '
                 f'{[float(f"{g:.3g}") for g in gaps[1]]}')
    return errors


def run(ctx):
    base = ctx.module('runners', 'serve_decode')
    base._logit_check = _logit_check
    return base.run(ctx)
