"""Runner `serve_decode_recurrent`: `serve_decode`, whole, for a model whose
layers keep a recurrent state per request and no row per token. Only the
logit check differs.

A state that decays wrongly, drifts, or is held in too few bits is right
after one step and wrong after sixty-four: each step multiplies the whole
state by its gate and adds one rank-one term, so an error of the state is
carried and compounded where a K/V row, once written, is never touched
again. And what is new in a state cache is the state on the chip at full
occupancy: a step walks every slot's own row of every layer, a prefill
overwrites one row among live neighbours, and a freed row is handed to the
next request. So the check drives the engine as the window does, in two
rounds, and holds EVERY prompt of both to the independent reference
(reference/<family>.py, float32 at precision "highest"):

  round 1  a prompt in every slot (`engine.slots` of them: the shortest and
           the longest the traffic allows, draws from its distribution
           between), prefilled one after the other into a row each, then
           stepped in lockstep, every slot live;
  round 2  every table released, then `check_prompts` new prompts on the
           rows just freed, in the last slots, the others idle on the
           scratch row.

Per prompt, THREE logits rows are held to the reference's whole-sequence
forward (the quadratic form) over the prompt and the system's own tokens:
the prefill's last row, the first decode step's row, and the row
`check_decode_steps` steps later (a key of the traffic file: 64, or one
more than the fold where a decode path folds a chunk of steps at a time).
The steps between are the system's own greedy steps through the engine, as
served. The tolerance is not widened for the distance and no row is
dropped.

A second limit, on the state itself (`check.state_tolerance`). The logits
cannot see in how many bits the state is held: a read contracts 8,256
coordinates and divides by its normaliser, so independent rounding errors
of the entries average out and a common one cancels (PERF.md section 6, PR
30). So the request's row of the FIRST state layer is read twice, as the
prefill left it (folded) and as the last checked step left it (walked),
and held entry by entry to what the reference says that layer's recurrence
holds after the same tokens (`reference.first_state`: plain float32 sums of
decayed outer products k kᵀ [v, 1], no φ, no chunk, no layout; the first
layer's k, v and gates are functions of the tokens alone): max |system -
reference| over max |reference|. The program says how its block stands for
those forms (`programs/<family>.py::first_state_forms`). A state held or
accumulated in fewer bits, decaying at another rate, written to or read
from a neighbour's row, or keeping something of the request that had the
row before, is far from it. The deeper layers' states follow inputs that
differ by the bf16 roundings of the layers below, and are not read.

Everything else (set-up, warm-up, load, window, the other checks, what is
returned) is runners/serve_decode.py::run, unedited, and the run says
'runner': 'serve_decode' so that every reader of that runner applies; the
state's limit is ANDed into its `correct`.
"""
from __future__ import annotations

import numpy as np


def _round(ctx, engine, prompts, slots, steps, rows_of, state_error):
    """``prompts`` prefilled in turn and stepped ``steps + 1`` times in
    lockstep in ``slots`` (the engine's other slots idle). Per prompt
    [length, error of the prefill's row, of step 1's, of the last step's]
    and [length, error of the folded state, of the walked one]."""
    tables = [None] * engine.slots
    seqs, got = {}, {}
    for slot, prompt in zip(slots, prompts):
        got[slot] = []

        def grab(row, keep=got[slot]):
            keep.append(np.array(row))
            return int(row.argmax())

        tables[slot] = engine.reserve_table(len(prompt), steps + 2)
        seqs[slot] = prompt + [engine.prefill(prompt, tables[slot],
                                              sampler=grab)]
    # after the LAST prefill: a later one must have left the earlier rows be
    folded = {slot: state_error(tables[slot], seqs[slot][:-1])
              for slot in slots}
    for step in range(steps + 1):
        ids, step_rows = engine.decode_step(
            [seqs[s][-1] if s in seqs else None
             for s in range(engine.slots)], tables, return_rows=True)
        for slot in slots:
            if step in (0, steps):
                got[slot].append(np.array(step_rows[slot]))
            seqs[slot].append(int(ids[slot]))
    errors, state_errors = [], []
    for slot, prompt in zip(slots, prompts):
        plen, tokens, row = len(prompt), seqs[slot][:-1], tables[slot].state_row
        state_errors.append([plen, folded[slot],
                             state_error(tables[slot], tokens)])
        engine.release_table(tables[slot])
        want = rows_of(tokens, [plen - 1, plen, plen + steps])
        scale = float(np.abs(want).max())
        errors.append([plen] + [float(np.abs(g - w).max()) / scale
                                for g, w in zip(got[slot], want)])
        ctx.info(f'logit check, slot {slot}, state row {row}, prompt {plen}: '
                 f'errors of the prefill row, step 1 and step {steps + 1}: '
                 f'{[round(e, 5) for e in errors[-1][1:]]}; of the first '
                 f'state layer, folded and walked: '
                 f'{[float(f"{e:.3g}") for e in state_errors[-1][1:]]}')
    return errors, state_errors


def _logit_check(ctx, engine, params, reference):
    """[prompt length, error of the prefill's last row, of the first decode
    step's row, of the row `check_decode_steps` steps later] as
    serve_decode's: max |system - reference| over max |reference| of the
    prompt's rows, for every prompt of the two rounds; the states' errors
    are left on ``ctx.state_errors``."""
    import jax
    import jax.numpy as jnp
    loadgen = ctx.module('lib', 'loadgen')
    forms = ctx.module('programs', ctx.config['family']).first_state_forms
    load = ctx.traffic['load']
    steps = ctx.traffic['check_decode_steps']
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    slots, again = engine.slots, ctx.traffic['check_prompts']
    lens = [load['prompt_len']['min'], load['prompt_len']['max']] \
        + [loadgen.quantile_len(load['prompt_len'], rng.random())
           for _ in range(slots + again - 2)]
    prompts = [rng.integers(1, load['vocab'], n).tolist() for n in lens]
    # one compiled reference for every length: the longest prompt and the
    # steps after it, in whole blocks
    block = engine.block_size
    pad = -(-(load['prompt_len']['max'] + steps + 1) // block) * block
    rows = reference.make_rows(ctx.config, pad)
    state = reference.make_state(ctx.config, pad)
    far = jax.jit(lambda got, want: jnp.max(jnp.abs(got - want))
                  / jnp.max(jnp.abs(want)))

    # "highest" for the reference alone: the engine's calls must run as
    # they are served
    def rows_of(tokens, positions):
        with jax.default_matmul_precision('highest'):
            return np.asarray(rows(params, tokens, positions))

    def state_error(table, tokens):
        with jax.default_matmul_precision('highest'):
            want = state(params, tokens)
        return float(far(forms(engine, table), want))

    # round 1: every slot live, the slots in the reverse of the rows' order
    errors, ctx.state_errors = _round(
        ctx, engine, prompts[:slots], list(range(slots))[::-1], steps,
        rows_of, state_error)
    # round 2: the rows just freed, in the last slots, the others idle
    more = _round(ctx, engine, prompts[slots:],
                  list(range(slots - again, slots)), steps, rows_of,
                  state_error)
    ctx.state_errors += more[1]
    return errors + more[0]


def run(ctx):
    base = ctx.module('runners', 'serve_decode')
    base._logit_check = _logit_check
    out = base.run(ctx)
    tol = ctx.config['check']['state_tolerance']
    within = bool(ctx.state_errors) and all(
        max(e[1:]) <= tol for e in ctx.state_errors)
    out['checks'].update(state_err_prompt_len_folded_walked=ctx.state_errors,
                         state_tolerance=tol, state_within_tolerance=within)
    out['correct'] = bool(out['correct'] and within)
    ctx.info('errors of the first state layer, worst of '
             f'{len(ctx.state_errors)} prompts, folded and walked: '
             f'{[max(e[i] for e in ctx.state_errors) for i in (1, 2)]} '
             f'against {tol}')
    return out
