"""Seeded weights, made on the device in one jitted call.

The program's layers draw each parameter with its own eager jax.random call
(~160 dispatches for ResNet-50). Here the model's constructor runs once under
jax.jit, with the program's key generator bound to the traced seed key, so
all parameters and buffers come out of ONE compiled program, in the type they
are kept in; the persistent cache holds that program after a checkout's first
run. The Layer objects the constructor made are kept and handed the results.
"""
import jax


def model_on_device(construct, seed):
    from paddle_tpu.core.random import default_generator
    made = {}

    def init(key):
        with default_generator.bind_base(key):
            made['model'] = construct()
        model = made['model']
        return ({n: p.value for n, p in model.named_parameters()},
                {n: b.value for n, b in model.named_buffers()})

    # seeding first also makes the generator's own base key a concrete
    # array: it builds that key lazily, and the first ask must not come from
    # inside a trace (a dropout op under TrainStep), where it would keep a
    # tracer
    default_generator.seed(seed)
    params, buffers = jax.jit(init)(default_generator.base_key())
    model = made['model']
    for n, p in model.named_parameters():
        p.value = params[n]
    for n, b in model.named_buffers():
        b.value = buffers[n]
    return model
