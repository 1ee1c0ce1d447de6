"""How unevenly a decode step's tokens fall on the experts: per step, the
worst layer's largest expert load over its mean load (program histogram
`decode_expert_load_max_over_mean{call="step"}`), mean over the window's
steps. 1 is even; with 128 slots x 6 over 128 experts the mean load is 6 and
a random routing reads about 2."""
NAME = 'expert_load_max_over_mean'
LAYER = 'decode_engine'
UNIT = 'ratio'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    metric = (run.get('registry') or {}).get(
        'decode_expert_load_max_over_mean')
    steps = [s for s in (metric or {}).get('samples', ())
             if s['labels'].get('call') == 'step' and s['count']]
    if not steps:
        return None
    return sum(s['sum'] for s in steps) / sum(s['count'] for s in steps)
