"""nemotron_h through the program's public API: HybridSSMMoELM at the sizes
of the configuration file (the published keys at its top level, with
`experts_held` and `router_width` beside them; the program's own under
`model`), in eval mode, as the decode engine serves it."""


def build(config):
    from paddle_tpu.models.hybrid_ssm_moe_lm import (HybridSSMMoEConfig,
                                                     HybridSSMMoELM)
    model = HybridSSMMoELM(HybridSSMMoEConfig.from_published(
        config, **config['model']))
    model.eval()
    return model


def first_conv_state(engine, table):
    """The row ``table`` holds in the engine's FIRST state layer (the first
    Mamba layer), flat as the values it stands for: S (H, P, N), then the
    filter's window (K - 1, conv_dim), oldest row first. The program's own
    word on its block's layout (ops/llm_ops.py::ssm_state_parts), for the
    check to hold to reference/nemotron_h.py::first_ssm_state."""
    import jax.numpy as jnp
    from paddle_tpu.ops.llm_ops import ssm_state_parts
    cfg = engine.model.cfg
    layers = engine.pool.arrays()[0]
    first = min(layer for layer, arrs in layers.items()
                if len(arrs) == 1 and arrs[0].ndim == 3)
    s, window = ssm_state_parts(layers[first][0][table.state_row],
                                cfg.mamba_head_dim, cfg.conv_dim,
                                cfg.conv_kernel)
    return jnp.concatenate([s.reshape(-1), window.reshape(-1)])
