"""The one cache layout each decode family declares (serving/decode/layout.py):
its counts, classes, span, ring and window; what a token, a block, a state
row, a request and the sliding class cost; and the refusal table, every
feature asked of every family. The byte figures are what the planner's
decode-pool functions gave for these models before the layout held them."""
import pytest

from paddle_tpu import dygraph
from paddle_tpu.core.random import default_generator
from paddle_tpu.serving.decode.engine import DecodeEngine
from paddle_tpu.serving.errors import UnsupportedCacheFeature


def _model(family):
    from paddle_tpu.models.block_diffusion_lm import (BlockDiffusionMoEConfig,
                                                      BlockDiffusionMoELM)
    from paddle_tpu.models.causal_lm import CausalLMConfig, TransformerLM
    from paddle_tpu.models.hybrid_conv_moe_lm import (HybridConvMoEConfig,
                                                      HybridConvMoELM)
    from paddle_tpu.models.latent_moe_lm import LatentMoEConfig, LatentMoELM
    from paddle_tpu.models.retention_lm import RetentionLM, RetentionLMConfig
    from paddle_tpu.models.sliding_moe_lm import (SlidingMoEConfig,
                                                  SlidingMoELM)
    default_generator.seed(7)
    model = {'gpt1': lambda: TransformerLM(CausalLMConfig.tiny()),
             'kanana2': lambda: LatentMoELM(LatentMoEConfig.tiny()),
             'brumby': lambda: RetentionLM(RetentionLMConfig.tiny()),
             'sdar': lambda: BlockDiffusionMoELM(
                 BlockDiffusionMoEConfig.tiny()),
             'trinity': lambda: SlidingMoELM(SlidingMoEConfig.tiny()),
             'lfm2': lambda: HybridConvMoELM(HybridConvMoEConfig.tiny()),
             }[family]()
    model.eval()
    return model


PREFIX = 'the prefix cache (and its spill and reinject)'
SPEC = 'speculative decoding (its (S, K) verify step)'
HANDOFF = 'the disaggregated handoff'

# family: (row, state, conv layers), (full, sliding, span), ring at blocks
# of 4, window, classes, reads; bytes at f32 of a token in a row layer, a
# block of 4, a state row, a request of 40 positions, the sliding class at
# 3 slots and blocks of 4; rows a step of 3 slots; and what each feature is
# refused as (absent: served)
LAYOUTS = {
    'gpt1': ((2, 0, 0), (2, 0, 0), 0, 1, False, (('blocks', 2),),
             (1024, 8192, None, 81920, 0), 3, {}),
    'kanana2': ((3, 0, 0), (3, 0, 0), 0, 1, False, (('groups', 3),),
                (512, 6144, None, 61440, 0), 3,
                {'prefix_cache': 'latent', 'int8': 'latent',
                 'handoff': 'latent'}),
    'brumby': ((0, 3, 0), (0, 0, 0), 0, 1, False, (),
               (0, 0, 9216, 9216, 0), 3,
               {'prefix_cache': 'state', 'spec_decode': 'state',
                'bf16': 'state', 'int8': 'state', 'handoff': 'state'}),
    'sdar': ((3, 0, 0), (3, 0, 0), 0, 4, False, (('window', 3),),
             (1024, 12288, None, 122880, 0), 12,
             {'prefix_cache': 'window', 'spec_decode': 'window',
              'int8': 'window', 'handoff': 'window'}),
    'trinity': ((4, 0, 0), (1, 3, 8), 3, 1, True,
                (('groups', 1), ('ring', 3)),
                (1024, 4096, None, 65536, 208896), 3,
                {'prefix_cache': 'sliding', 'spec_decode': 'sliding',
                 'int8': 'sliding', 'handoff': 'sliding'}),
    'lfm2': ((1, 3, 3), (1, 0, 0), 0, 1, True, (('groups', 1),),
             (1024, 4096, 768, 41728, 0), 3,
             {'prefix_cache': 'state', 'spec_decode': 'state',
              'int8': 'grouped', 'handoff': 'state'}),
}

# feature: (the engine's arguments, or None for the handoff; what is named)
FEATURES = {'prefix_cache': ({'prefix_cache': True}, PREFIX),
            'spec_decode': ({'spec_decode': True}, SPEC),
            'bf16': ({'kv_dtype': 'bf16'}, 'kv_dtype=bf16'),
            'int8': ({'kv_dtype': 'int8'}, 'kv_dtype=int8'),
            'handoff': (None, HANDOFF)}


@pytest.mark.parametrize('family', sorted(LAYOUTS))
def test_each_family_declares_one_layout_and_the_one_table_refuses(family):
    (counts, classes, ring, window, classed, reads, costs, step_rows,
     refused) = LAYOUTS[family]
    with dygraph.guard():
        model = _model(family)
        layout = model.cache_layout()
        assert (layout.row_layers, layout.state_layers,
                layout.conv_layers) == counts
        assert (layout.full_layers, layout.sliding_layers,
                layout.span) == classes
        assert layout.ring(4) == ring and layout.window == window
        assert layout.classes is classed and layout.reads == reads
        token, block, state_row, request, sliding = costs
        assert layout.token_bytes() == token
        assert layout.block_bytes(4) == block
        if state_row is None:
            with pytest.raises(ValueError, match='no state row'):
                layout.state_row_bytes()
        else:
            assert layout.state_row_bytes() == state_row
        assert layout.request_bytes(40) == request
        assert layout.sliding_class_bytes(3, 4) == sliding
        assert layout.step_rows(3) == step_rows
        for feature, (asked, named) in FEATURES.items():
            args = dict(slots=3, block_size=4, max_blocks=64,
                        max_prompt_len=16, max_new_tokens_cap=12,
                        prompt_buckets=[4, 8, 16], prefix_cache=False)
            args.update(asked or {})
            try:
                engine = DecodeEngine(model, **args)
                if asked is None:
                    from paddle_tpu.serving.tier.disagg import PrefillReplica
                    PrefillReplica(engine)
            except UnsupportedCacheFeature as e:
                assert (feature, e.kind) == (feature, refused.get(feature))
                assert e.features == [named]
                assert str(e).startswith(f'{named} cannot be used with ')
                continue
            assert feature not in refused, feature
            assert engine.layout == layout
