"""Runner `serve_decode_routed`: `serve_decode`, whole, for a model with
routed experts. Only the logit check differs.

With random weights the router's choice between the last expert chosen and
the first left out flips on rounding, as an argmax does: on the chip a
served bf16 model decides 6-9% of its choices otherwise than the float32
reference (PERF.md section 6, PR 26), and one other expert moves a logits
row by far more than any honest tolerance. The tolerance is not widened for
it and no row is dropped. The engine reports which experts made the rows it
scored (`engine.last_stats['expert_ids']`), and the reference
(reference/<family>.py) follows such a choice only where its OWN scores call
it a near-tie, every chosen expert within the configuration's `tie_margin`
of every expert left out; elsewhere its own choice stands and the row is far
from the system's. The decode row is computed with the prefill's choice at
the position before it followed too, since the step reads the latent row
the prefill wrote under it. A router of lower precision is not followed, and
weights of lower precision are outside the tolerance on a followed row.

Everything else (set-up, warm-up, load, window, the other checks, what is
returned) is runners/serve_decode.py::run, unedited, and the run says
'runner': 'serve_decode' so that every reader of that runner applies.
"""
from __future__ import annotations

import numpy as np


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the prefill's last row, error of one decode
    step] as serve_decode's: max |paged - reference| over max |reference|,
    the reference's rows computed under the system's own choices of experts
    at the two positions, where the reference calls them near-ties."""
    import jax
    loadgen = ctx.module('lib', 'loadgen')
    load = ctx.traffic['load']
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    n = ctx.traffic['check_prompts']
    lens = [load['prompt_len']['min']] \
        + [loadgen.quantile_len(load['prompt_len'], rng.random())
           for _ in range(n - 2)] \
        + [load['prompt_len']['max']]
    block = engine.block_size
    rows = reference.make_rows(
        ctx.config, -(-(load['prompt_len']['max'] + 1) // block) * block)
    tie_margin = ctx.config['check']['tie_margin']
    errors = []
    for plen in lens:
        prompt = rng.integers(1, load['vocab'], plen).tolist()
        got, chosen = [], {}

        def grab(row):
            got.append(np.array(row))
            return int(row.argmax())

        table = engine.reserve_table(plen, 2)
        token = engine.prefill(prompt, table, sampler=grab)
        chosen[plen - 1] = np.asarray(engine.last_stats['expert_ids'])[:, 0]
        tokens = [token] + [None] * (engine.slots - 1)
        tables = [table] + [None] * (engine.slots - 1)
        _, step_rows = engine.decode_step(tokens, tables, return_rows=True)
        chosen[plen] = np.asarray(engine.last_stats['expert_ids'])[:, 0]
        got.append(np.array(step_rows[0]))
        engine.release_table(table)
        # "highest" for the reference alone: the engine's calls above must
        # run as they are served
        with jax.default_matmul_precision('highest'):
            want, gaps = rows(params, prompt + [token], [plen - 1, plen],
                              chosen, tie_margin)
        want, gaps = np.asarray(want), np.asarray(gaps)
        scale = float(np.abs(want).max())
        entry = [plen] + [float(np.abs(g - w).max()) / scale
                          for g, w in zip(got, want)]
        errors.append(entry)
        # every prompt's line: the gaps are what `tie_margin` is set from (a
        # gap above zero is a choice the reference would have made the other
        # way; above `tie_margin` it was not followed)
        ctx.info(f'logit check, prompt {plen}: errors '
                 f'{[round(e, 5) for e in entry[1:]]}; gaps of the '
                 f"system's choices by expert layer, prefill row "
                 f'{[round(float(g), 5) for g in gaps[0]]}, decode row '
                 f'{[round(float(g), 5) for g in gaps[1]]}')
    return errors


def run(ctx):
    base = ctx.module('runners', 'serve_decode')
    base._logit_check = _logit_check
    return base.run(ctx)
