"""Runner `serve_decode_sliding`: `serve_decode`, whole, for a model with
layer classes (sliding and full attention layers over one pool manager) and
routed experts of which it holds a share. Only the logit check differs.

What the check must see that serve_decode_routed's does not: the RING. A
sliding layer keeps a request's last `sliding_window` positions in a ring of
blocks and overwrites it in place; a fault there (a block given to the wrong
position, an edge of the mask off by one, a row read after the ring came
round) shows only once a context has passed the window, and in a decode step
more than in the prefill, which attends its own projections. So the prompts
are the shortest the traffic allows (the ring never comes round), the
longest (it has come round inside the prefill), `check_edge_prompt` (a few
tokens short of the window: decoding crosses its edge inside the check) and
draws from the distribution between; and each is decoded for `check_steps`
steps through the cache, the rows of the prefill, of step 1 and of the last
step held against the reference's whole-sequence forward over the system's
own tokens.

The experts' near-ties are judged as serve_decode_routed.py's docstring
says (the reference follows a reported choice only where its OWN scores call
it a near-tie, `tie_margin`), at every position the check fed: the prefill's
last and each decode step's, since a later step reads the K/V rows the
earlier ones wrote under their choices.

Everything else (set-up, warm-up, load, window, the other checks, what is
returned) is runners/serve_decode.py::run, unedited, and the run says
'runner': 'serve_decode' so that every reader of that runner applies.
"""
from __future__ import annotations

import numpy as np


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the prefill's last row, of decode step 1, of
    decode step `check_steps`] as max |paged - reference| over max
    |reference|, the reference's rows computed under the system's own
    choices of experts where the reference calls them near-ties."""
    import jax
    loadgen = ctx.module('lib', 'loadgen')
    load, traffic = ctx.traffic['load'], ctx.traffic
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    steps = traffic['check_steps']
    lo, hi = load['prompt_len']['min'], load['prompt_len']['max']
    lens = [lo, hi, traffic['check_edge_prompt']] \
        + [loadgen.quantile_len(load['prompt_len'], rng.random())
           for _ in range(traffic['check_prompts'] - 3)]
    rows = reference.make_rows(ctx.config, reference.pad_of(hi + steps))
    tie_margin = ctx.config['check']['tie_margin']
    idle = [None] * (engine.slots - 1)
    errors = []
    for plen in lens:
        prompt = rng.integers(1, load['vocab'], plen).tolist()
        got, chosen = [], {}

        def grab(row):
            got.append(np.array(row))
            return int(row.argmax())

        table = engine.reserve_table(plen, steps + 1)
        fed = [engine.prefill(prompt, table, sampler=grab)]
        chosen[plen - 1] = np.asarray(engine.last_stats['expert_ids'])[:, 0]
        for step in range(1, steps + 1):
            picks, step_rows = engine.decode_step(
                [fed[-1]] + idle, [table] + idle, return_rows=True)
            chosen[plen - 1 + step] = np.asarray(
                engine.last_stats['expert_ids'])[:, 0]
            if step in (1, steps):
                got.append(np.array(step_rows[0]))
            fed.append(int(picks[0]))
        engine.release_table(table)
        at = [plen - 1, plen] + ([plen - 1 + steps] if steps > 1 else [])
        # "highest" for the reference alone: the engine's calls above must
        # run as they are served
        with jax.default_matmul_precision('highest'):
            want, gaps = rows(params, prompt + fed[:-1], at, chosen,
                              tie_margin)
        want, gaps = np.asarray(want), np.asarray(gaps)
        scale = float(np.abs(want).max())
        entry = [plen] + [float(np.abs(g - w).max()) / scale
                          for g, w in zip(got, want)]
        errors.append(entry)
        # every prompt's line: the gaps are what `tie_margin` is set from
        ctx.info(f'logit check, prompt {plen}: errors '
                 f'{[round(e, 5) for e in entry[1:]]} (prefill, step 1, '
                 f"step {steps}); gaps of the system's choices by expert "
                 f'layer at those rows '
                 f'{[[round(float(g), 5) for g in row] for row in gaps]}')
    return errors


def run(ctx):
    base = ctx.module('runners', 'serve_decode')
    base._logit_check = _logit_check
    return base.run(ctx)
