"""Output tokens received by the clients in the window, over the window."""
NAME = 'serve_tokens_per_s'
UNIT = 'tokens/s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    return run['counts']['tokens'] / run['window_s']
