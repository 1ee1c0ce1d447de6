"""Bytes of logits the engine copied from the device to the host for each
token it emitted: counter decode_logits_bytes_copied over counter
decode_tokens_generated, both over the window. A step copies (S, 1, V) float32
for its S rows; a prefill copies its whole (1, bucket, V) block for the one
row that is sampled. One row is V x 4 bytes (161,912 at GPT-1's vocabulary); a
step that samples on the device copies 4."""
NAME = 'logits_copy_bytes_per_token'
LAYER = 'decode_engine'
UNIT = 'bytes/token'
MOVES = 'serve_tokens_per_s'
RUNNERS = ('serve_decode',)


def read(run, ctx):
    counter = ctx.module('lib', 'decode_phases').counter
    copied = counter(run, 'decode_logits_bytes_copied')
    tokens = counter(run, 'decode_tokens_generated')
    if copied is None or not tokens:
        return None
    return copied / tokens
