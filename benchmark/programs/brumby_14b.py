"""brumby_14b through the program's public API: RetentionLM at the sizes of
the configuration file (the published keys at its top level, the program's
own under `model`), in eval mode, as the decode engine serves it."""
import functools


def build(config):
    from paddle_tpu.models.retention_lm import RetentionLM, RetentionLMConfig
    model = RetentionLM(RetentionLMConfig.from_published(config,
                                                         **config['model']))
    model.eval()
    return model


@functools.lru_cache(None)
def _forms():
    import jax
    from paddle_tpu.ops.llm_ops import retention_state_forms
    return jax.jit(lambda states, row: retention_state_forms(states[row]))


def first_state_forms(engine, table):
    """The row ``table`` holds in the engine's FIRST state layer, as the
    quadratic forms (G, d, d, d + 1) it stands for: the program's own word
    on its block's layout (ops/llm_ops.py::retention_state_forms), for the
    check to hold to reference/brumby_14b.py::first_state."""
    layers = engine.pool.arrays()[0]
    first = min(layer for layer, arrs in layers.items()
                if len(arrs) == 1 and arrs[0].ndim == 4)
    return _forms()(layers[first][0], table.state_row)
