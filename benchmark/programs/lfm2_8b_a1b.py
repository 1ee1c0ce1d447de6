"""lfm2_8b_a1b through the program's public API: HybridConvMoELM at the
sizes of the configuration file (the published keys at its top level, the
program's own under `model`), in eval mode, as the decode engine serves it."""


def build(config):
    from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                      HybridConvMoELM)
    model = HybridConvMoELM(HybridConvMoEConfig.from_published(
        config, **config['model']))
    model.eval()
    return model


def first_conv_state(engine, table):
    """The row ``table`` holds in the engine's FIRST state layer, as the
    (L - 1, h) values of u it stands for, oldest first: the program's own
    word on its block's layout (ops/llm_ops.py "gated short convolution":
    a block (1, L - 1, h)), for the check to hold to
    reference/lfm2_8b_a1b.py::first_conv_state."""
    layers = engine.pool.arrays()[0]
    first = min(layer for layer, arrs in layers.items()
                if len(arrs) == 1 and arrs[0].ndim == 4)
    return layers[first][0][table.state_row, 0]
