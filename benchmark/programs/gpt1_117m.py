"""gpt1_117m through the program's public API: TransformerLM at the sizes of
the configuration file, in eval mode, as the decode engine serves it."""


def build(config):
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    model = TransformerLM(CausalLMConfig(**config['model']))
    model.eval()
    return model
