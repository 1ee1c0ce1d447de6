"""resnet50 through the program's public API: the model, its loss, its
optimizer and a seeded batch. Every size comes from the configuration file."""
import jax
import jax.numpy as jnp


def build(config):
    from paddle_tpu.models.resnet import ResNet
    m = config['model']
    return ResNet(m['depth'], m['class_dim'], data_format=m['data_format'])


def loss_fn(model, image, label):
    from paddle_tpu.dygraph.tape import dispatch_op
    logits = dispatch_op('cast', {'x': model(image)}, {'dtype': 'float32'})
    loss, _ = dispatch_op('softmax_with_cross_entropy',
                          {'logits': logits, 'label': label}, {})
    return dispatch_op('reduce_mean', {'x': loss}, {})


def optimizer(config, model):
    import paddle_tpu as fluid
    o = config['optimizer']
    return fluid.optimizer.Momentum(o['learning_rate'],
                                    momentum=o['momentum'],
                                    parameter_list=model.parameters())


def batch(key, config, traffic, n):
    """(image, label) for n samples: normal pixels in the compute type (the
    input pipeline's output type), uniform labels."""
    m = config['model']
    k1, k2 = jax.random.split(key)
    size = traffic['image_size']
    image = jax.random.normal(k1, (n, size, size, 3),
                              jnp.dtype(config['dtype_policy']['compute']))
    label = jax.random.randint(k2, (n, 1), 0, m['class_dim'], jnp.int32)
    return image, label
