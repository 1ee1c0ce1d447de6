"""Analytic training FLOPs of bert_base per sequence (copied from bench.py's
bench_bert): forward + backward = 3 x forward, 2 FLOPs per multiply-add,
recomputed operations not counted. Per token:

  72 L h^2     the blocks' matmuls (QKVO 4 h^2 + FFN 8 h^2 parameters, x 6)
  12 L h S     attention scores and context (2 S h multiply-adds each, x 6)
  6 h V        the MLM decoder over every position, as the program computes it

Left out, under 1% together: the MLM transform (6 h^2), pooler, NSP head,
embeddings, LayerNorm, softmax, Adam.
"""


def per_sample(config, traffic):
    m, s = config['model'], traffic['seq_len']
    h, layers, vocab = (m['hidden_size'], m['num_hidden_layers'],
                        m['vocab_size'])
    return s * (72.0 * layers * h * h + 12.0 * layers * h * s
                + 6.0 * h * vocab)
