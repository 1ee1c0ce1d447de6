"""trinity_large_preview through the program's public API: SlidingMoELM at
the sizes of the configuration file (the published keys at its top level,
with `experts_held` and `router_width` beside them; the program's own under
`model`), in eval mode, as the decode engine serves it."""


def build(config):
    from paddle_tpu.models.sliding_moe_lm import (SlidingMoEConfig,
                                                  SlidingMoELM)
    model = SlidingMoELM(SlidingMoEConfig.from_published(config,
                                                         **config['model']))
    model.eval()
    return model
