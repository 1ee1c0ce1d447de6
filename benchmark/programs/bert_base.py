"""bert_base through the program's public API: BertForPretraining, its
MLM+NSP loss, Adam, and a seeded pre-training batch. Every size comes from
the configuration file."""
import jax
import jax.numpy as jnp


def build(config):
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    return BertForPretraining(BertConfig(**config['model']))


def loss_fn(model, input_ids, token_type_ids, mlm_labels, nsp_labels):
    from paddle_tpu.models.bert import pretrain_loss
    return pretrain_loss(model, input_ids, token_type_ids, mlm_labels,
                         nsp_labels)


def optimizer(config, model):
    import paddle_tpu as fluid
    o = config['optimizer']
    return fluid.optimizer.Adam(o['learning_rate'], beta1=o['beta1'],
                                beta2=o['beta2'], epsilon=o['epsilon'],
                                parameter_list=model.parameters())


def batch(key, config, traffic, n):
    """(input_ids, token_type_ids, mlm_labels, nsp_labels) for n sequences.
    Every sequence has the same number of masked positions (15%), so the MLM
    mean over any equal split of the batch is the mean over the batch: that
    is what lets the reference accumulate over shards, and a dp mesh equal
    one chip. Labels are -1 where nothing is masked."""
    vocab, seq = config['model']['vocab_size'], traffic['seq_len']
    masked = max(int(seq * traffic['masked_share']), 1)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    ids = jax.random.randint(k1, (n, seq), 0, vocab, jnp.int32)
    first = jax.random.randint(k2, (n, 1), 1, seq, jnp.int32)
    segment = (jnp.arange(seq)[None, :] >= first).astype(jnp.int32)
    order = jnp.argsort(jax.random.uniform(k3, (n, seq)), axis=1)
    is_masked = order < masked
    targets = jax.random.randint(k4, (n, seq), 0, vocab, jnp.int32)
    mlm = jnp.where(is_masked, targets, -1)
    nsp = jax.random.randint(k5, (n, 1), 0, 2, jnp.int32)
    return ids, segment, mlm, nsp
