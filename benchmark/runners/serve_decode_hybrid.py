"""Runner `serve_decode_hybrid`: `serve_decode`, whole, for a HYBRID model:
state layers (a gated short convolution: a fixed-size state a request)
beside row layers (grouped-head attention: K/V rows a token) over one cache
manager, and routed experts. Only the logit check differs.

What the check must see that serve_decode_routed's and _sliding's do not:
the STATE a prefill leaves, and the row it leaves it in. A prefill runs on a
ladder rung: the prompt is padded to the rung, and what the request carries
on is (u_{P-2}, u_{P-1}) of its TRUE end, never the rung's last rows; the
first decode steps read exactly that (step 1 reads both values, step 2's
u_{t-2} is the prompt's last), and nothing later does. And rows turn over:
a table is taken right after the last was released, so the state row is one
another request has just used. So the prompts are the shortest and the
longest the traffic allows, `check_edge_prompts` (1,025: the 2,048 rung
nearly half padding; 4,095: one short of the top rung) and draws from the
distribution between; each is prefilled and decoded `check_steps` steps
through the cache, and held to the reference's whole-sequence forward over
the system's own tokens: the logits rows of the prefill's last position, of
steps 1 and 2 and of the last step, as max |paged - reference| over max
|reference|, and the request's row of the FIRST conv layer right after the
prefill against `reference.first_conv_state` (plain products of that
layer's own rows), as max |system - reference| over max |reference|
(`check.state_tolerance`). The program says how its row stands for those
values (`programs/<family>.py::first_conv_state`).

The experts' near-ties are judged as serve_decode_routed.py's docstring
says (the reference follows a reported choice only where its OWN scores call
it a near-tie, `tie_margin`), at every position of the prompt and at every
position the check fed. Every position, not the checked rows alone: a conv
layer's row reads the two rows before it directly, where an attention layer
averages over its context, so a near-tie that the bf16 system resolved the
other way at position P - 2 moves the prefill's row and step 1's by 0.05-0.1
of the largest logit (seen on the chip with the checked rows alone followed:
PERF.md section 6, PR 38), and steps 2 and 16, whose neighbours were fed by
the check, by nothing. The program notes every row's choices in a prefill
(`engine.last_stats['expert_ids']`, (expert layers, rung, k)).

Everything else (set-up, warm-up, load, window, the other checks, what is
returned) is runners/serve_decode.py::run, unedited, and the run says
'runner': 'serve_decode' so that every reader of that runner applies; the
state's limit is ANDed into its `correct`.
"""
from __future__ import annotations

import numpy as np


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the prefill's last row, of decode steps 1,
    2 and `check_steps`] as max |paged - reference| over max |reference|,
    the reference's rows computed under the system's own choices of experts
    where the reference calls them near-ties; the first conv layer's state
    errors are left on ``ctx.state_errors``."""
    import jax
    loadgen = ctx.module('lib', 'loadgen')
    state_of = ctx.module('programs', ctx.config['family']).first_conv_state
    load, traffic = ctx.traffic['load'], ctx.traffic
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    steps = traffic['check_steps']
    lo, hi = load['prompt_len']['min'], load['prompt_len']['max']
    edges = list(traffic['check_edge_prompts'])
    lens = [lo, hi] + edges + [
        loadgen.quantile_len(load['prompt_len'], rng.random())
        for _ in range(traffic['check_prompts'] - 2 - len(edges))]
    pad = reference.pad_of(hi + steps)
    rows = reference.make_rows(ctx.config, pad)
    state = reference.make_first_conv_state(ctx.config, pad)
    tie_margin = ctx.config['check']['tie_margin']
    idle = [None] * (engine.slots - 1)
    kept = sorted({1, 2, steps})
    errors, ctx.state_errors = [], []
    for plen in lens:
        prompt = rng.integers(1, load['vocab'], plen).tolist()
        got, chosen = [], {}

        def grab(row):
            got.append(np.array(row))
            return int(row.argmax())

        # taken right after the last was released: a reused state row
        table = engine.reserve_table(plen, steps + 1)
        fed = [engine.prefill(prompt, table, sampler=grab)]
        # (expert layers, rung, k): every row of the prompt, the rung's
        # padding left out
        noted = np.asarray(engine.last_stats['expert_ids'])
        chosen.update((at, noted[:, at]) for at in range(plen))
        held = np.asarray(state_of(engine, table))
        for step in range(1, steps + 1):
            picks, step_rows = engine.decode_step(
                [fed[-1]] + idle, [table] + idle, return_rows=True)
            chosen[plen - 1 + step] = np.asarray(
                engine.last_stats['expert_ids'])[:, 0]
            if step in kept:
                got.append(np.array(step_rows[0]))
            fed.append(int(picks[0]))
        row = table.state_row
        engine.release_table(table)
        at = [plen - 1] + [plen - 1 + step for step in kept]
        # "highest" for the reference alone: the engine's calls above must
        # run as they are served
        with jax.default_matmul_precision('highest'):
            want, gaps = rows(params, prompt + fed[:-1], at, chosen,
                              tie_margin)
            carried = np.asarray(state(params, prompt))
        want, gaps = np.asarray(want), np.asarray(gaps)
        scale = float(np.abs(want).max())
        entry = [plen] + [float(np.abs(g - w).max()) / scale
                          for g, w in zip(got, want)]
        errors.append(entry)
        ctx.state_errors.append(
            [plen, float(np.abs(held - carried).max())
             / float(np.abs(carried).max())])
        # every prompt's line: the gaps are what `tie_margin` is set from
        ctx.info(f'logit check, prompt {plen}, state row {row}: errors '
                 f'{[round(e, 5) for e in entry[1:]]} (prefill, steps '
                 f'{kept}); of the first conv state after the prefill '
                 f'{ctx.state_errors[-1][1]:.3g}; gaps of the system\'s '
                 f'choices by expert layer at those rows '
                 f'{[[round(float(g), 5) for g in r] for r in gaps]}')
    return errors


def run(ctx):
    base = ctx.module('runners', 'serve_decode')
    base._logit_check = _logit_check
    out = base.run(ctx)
    tol = ctx.config['check']['state_tolerance']
    within = bool(ctx.state_errors) and all(
        e[1] <= tol for e in ctx.state_errors)
    out['checks'].update(conv_state_err_prompt_len=ctx.state_errors,
                         state_tolerance=tol, state_within_tolerance=within)
    out['correct'] = bool(out['correct'] and within)
    ctx.info('errors of the first conv state after the prefill, worst of '
             f'{len(ctx.state_errors)} prompts: '
             f'{max(e[1] for e in ctx.state_errors)} against {tol}')
    return out
