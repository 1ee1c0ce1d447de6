"""kanana2_30b_a3b through the program's public API: LatentMoELM at the sizes
of the configuration file (the published keys at its top level, the
program's own under `model`), in eval mode, as the decode engine serves it."""


def build(config):
    from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
    model = LatentMoELM(LatentMoEConfig.from_published(config,
                                                       **config['model']))
    model.eval()
    return model
