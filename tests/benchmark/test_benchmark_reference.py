"""Each plain reference (benchmark/reference/) against the program at a tiny
size, in float32 on the CPU, where the two must agree to rounding. bert_base
and gpt1_117m are held by the rehearsals of test_benchmark_harness.py (their
`correct` is that comparison); this file adds ResNet-50, whose trace and
compile is the slow one, and shows that the checks bite."""
import numpy as np

from bench_testlib import load, rehearse


def test_resnet50_reference_agrees_and_a_skipped_update_would_fail(capsys):
    rc, last, out = rehearse(capsys, 'tiny_resnet_train')
    assert rc == 0 and last['correct'] is True, out[-3000:]
    import json
    checks = json.loads(out.split('[info] checks: ')[1].splitlines()[0])
    assert checks['loss0_rel_err'] < 1e-4 and checks['loss1_rel_err'] < 1e-4
    # the second loss depends on the update: without it, it would sit far
    # outside the tolerance
    assert checks['update_moves_loss1_by'] > 10 * checks['tolerance']


def test_decoder_reference_is_causal_and_sees_every_layer():
    import jax
    import jax.numpy as jnp
    ref = load('reference/gpt1_117m.py')
    model = {'num_hidden_layers': 2, 'num_attention_heads': 2,
             'max_position_embeddings': 16}
    config = {'model': model}
    h, v = 8, 11
    rng = np.random.RandomState(0)
    names = ['word_emb.weight', 'pos_emb.weight', 'emb_ln.weight',
             'emb_ln.bias']
    shapes = [(v, h), (16, h), (h,), (h,)]
    for i in range(2):
        for lin, (a, b) in {'attn.q': (h, h), 'attn.k': (h, h),
                            'attn.v': (h, h), 'attn.out': (h, h),
                            'ffn1': (h, 4 * h), 'ffn2': (4 * h, h)}.items():
            names += [f'blocks.{i}.{lin}.weight', f'blocks.{i}.{lin}.bias']
            shapes += [(a, b), (b,)]
        for ln in ('attn_ln', 'ffn_ln'):
            names += [f'blocks.{i}.{ln}.weight', f'blocks.{i}.{ln}.bias']
            shapes += [(h,), (h,)]
    params = {n: jnp.asarray(rng.randn(*s), jnp.float32)
              for n, s in zip(names, shapes)}
    rows = ref.make_rows(config)
    ids = [3, 5, 7, 2, 9]
    base = np.asarray(rows(params, ids, [0, 1, 2]))
    # causal: changing a later token leaves earlier rows alone
    later = np.asarray(rows(params, ids[:3] + [1, 1], [0, 1, 2]))
    np.testing.assert_allclose(base, later, rtol=1e-6, atol=1e-6)
    # every layer counts: perturbing the last block's weights moves the rows
    params['blocks.1.ffn2.weight'] = params['blocks.1.ffn2.weight'] * 1.5
    assert np.abs(np.asarray(rows(params, ids, [0, 1, 2])) - base).max() > 1e-3
    assert jax.default_backend() == 'cpu'
