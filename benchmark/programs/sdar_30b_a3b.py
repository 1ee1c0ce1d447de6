"""sdar_30b_a3b through the program's public API: BlockDiffusionMoELM at the
sizes of the configuration file (the published keys at its top level, the
program's own under `model`), in eval mode, as the decode engine serves
it."""


def build(config):
    from paddle_tpu.models.block_diffusion_lm import (
        BlockDiffusionMoEConfig, BlockDiffusionMoELM)
    model = BlockDiffusionMoELM(BlockDiffusionMoEConfig.from_published(
        config, **config['model']))
    model.eval()
    return model
