"""Op-level contract of ops/llm_ops.py's gated short convolution: the
whole-sequence form of a prefill and the one-token form of a step, each
against a float64 numpy walk of

    u_t = B_t ⊙ z_t,   c_t = Σ_j w[j] ⊙ u_{t-(L-1)+j},   y_t = C_t ⊙ c_t

a token at a time over an explicit window of the last L values of u; the
state a prefill leaves is the prompt's TRUE end's, the rows' own and the
scratch row under a step, the analysis rules, and `moe_router`'s
``norm_epsilon``."""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.llm_ops import (moe_router, short_conv_prefill,
                                    short_conv_step)

H = 16


def draw(rng, length, taps=3, batch=1):
    x = rng.standard_normal((batch, length, 3 * H)).astype('float32')
    w = rng.standard_normal((taps, H)).astype('float32')
    return x, w


def walk(x, w, window=None):
    """One sequence x (T, 3h) a token at a time in float64: (y (T, h), the
    last L - 1 values of u, oldest first)."""
    x, w = np.asarray(x, 'float64'), np.asarray(w, 'float64')
    taps = len(w)
    window = np.zeros((taps - 1, H)) if window is None else window.copy()
    out = np.zeros((len(x), H))
    for t, row in enumerate(x):
        b, c, z = row[:H], row[H:2 * H], row[2 * H:]
        window = np.concatenate([window, (b * z)[None]])      # L values
        out[t] = c * (w * window).sum(0)
        window = window[1:]
    return out, window


@pytest.mark.parametrize('taps', [2, 3, 4])
@pytest.mark.parametrize('length', [1, 2, 7])
def test_prefill_equals_the_walk(length, taps):
    rng = np.random.default_rng([length, taps])
    x, w = draw(rng, length, taps, batch=2)
    out, state = short_conv_prefill(x, w)
    assert out.shape == (2, length, H) and state.shape == (2, 1, taps - 1, H)
    assert state.dtype == jnp.float32
    for b in range(2):
        want, kept = walk(x[b], w)
        np.testing.assert_allclose(out[b], want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(state[b, 0], kept, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize('last', [0, 1, 2, 5, 6, 7])
def test_the_state_is_of_the_prompts_true_end_not_the_rungs(last):
    """A prompt of last + 1 tokens padded to a rung of 8: the rows up to
    ``last`` are what the unpadded prompt gives, whatever the padding
    holds, and the state is (u_{last-1}, u_{last}), zero where the prompt
    has one token."""
    rng = np.random.default_rng(last)
    x, w = draw(rng, 8)
    x[0, last + 1:] = 1e3 * rng.standard_normal((7 - last, 3 * H))
    out, state = short_conv_prefill(x, w, np.int32(last))
    want, kept = walk(x[0, :last + 1], w)
    np.testing.assert_allclose(out[0, :last + 1], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state[0, 0], kept, rtol=1e-6, atol=1e-7)
    if last == 0:
        assert not np.asarray(state[0, 0, 0]).any()
    if last < 7:
        # the rung's own end is another state: the padded rows'
        rung = short_conv_prefill(x, w)[1]
        assert np.abs(np.asarray(rung - state)).max() > 1.0


@pytest.mark.parametrize('taps', [2, 3, 4])
def test_step_iterated_equals_prefill(taps):
    """A prefill of 5 tokens then 6 steps, in slots that hold rows 3 and 1
    of a state array of 5 rows, equals the prefill of all 11: outputs and
    the final state; the rows nobody holds stay as they were."""
    rng = np.random.default_rng(taps)
    x, w = draw(rng, 11, taps, batch=2)
    whole, final = short_conv_prefill(x, w)
    out, after = short_conv_prefill(x[:, :5], w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(whole[:, :5]))
    state = jnp.full((5, 1, taps - 1, H), 7.0)
    rows = jnp.asarray([3, 1], jnp.int32)
    state = state.at[rows].set(after)
    for t in range(5, 11):
        y, state = short_conv_step(x[:, t:t + 1], w, state, rows)
        assert y.shape == (2, 1, H)
        np.testing.assert_allclose(y, whole[:, t:t + 1], rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(state[rows], final, rtol=1e-6)
    assert (np.asarray(state)[[0, 2, 4]] == 7.0).all()


def test_idle_slots_write_the_scratch_row_alone():
    rng = np.random.default_rng(5)
    x, w = draw(rng, 1, batch=4)
    state = jnp.asarray(rng.standard_normal((6, 1, 2, H)), jnp.float32)
    rows = jnp.asarray([0, 4, 0, 0], jnp.int32)        # one live slot
    _, after = short_conv_step(x.reshape(4, 1, 3 * H), w, state, rows)
    before, after = np.asarray(state), np.asarray(after)
    assert (after[[1, 2, 3, 5]] == before[[1, 2, 3, 5]]).all()
    u = x[1, 0, :H] * x[1, 0, 2 * H:]
    np.testing.assert_allclose(after[4, 0], [before[4, 0, 1], u], rtol=1e-6)
    assert not (after[0] == before[0]).all()           # scratch: anyone's


def test_products_and_state_are_float32_whatever_the_rows_are():
    """bf16 rows (the served path): the output returns in bf16, the state
    is the float32 product of the rows as stored, to the bit, not a bf16
    rounding of it."""
    rng = np.random.default_rng(9)
    x, w = draw(rng, 6)
    xb = jnp.asarray(x, jnp.bfloat16)
    out, state = short_conv_prefill(xb, jnp.asarray(w, jnp.bfloat16))
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    xf = np.asarray(xb, 'float32')
    u = xf[0, :, :H] * xf[0, :, 2 * H:]
    assert (np.asarray(state[0, 0]) == u[-2:]).all()
    assert (np.asarray(state.astype(jnp.bfloat16), 'float32')
            != np.asarray(state)).any()


# -- the router's normaliser ---------------------------------------------------

def _router_inputs():
    rng = np.random.default_rng(3)
    return (rng.standard_normal((5, 8)).astype('float32'),
            rng.standard_normal((8, 6)).astype('float32'),
            0.05 * rng.standard_normal(6).astype('float32'))


def test_router_norm_epsilon_default_is_what_it_was():
    x, w, b = _router_inputs()
    ids, weights = moe_router(x, w, b, top_k=2, routed_scaling_factor=2.5)
    again = moe_router(x, w, b, top_k=2, routed_scaling_factor=2.5,
                       norm_epsilon=1e-20)
    s = 1 / (1 + np.exp(-(x.astype('float64') @ w)))
    chosen = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(again[1]))
    # 1e-20 adds nothing to a float32 sum of sigmoids: the weights sum to
    # the scaling factor to float32's rounding
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1,
                                                            keepdims=True)
                               * 2.5, rtol=1e-6)


def test_router_norm_epsilon_is_added_to_the_chosen_scores_sum():
    x, w, b = _router_inputs()
    ids, weights = moe_router(x, w, b, top_k=2, norm_epsilon=1e-6)
    _, plain = moe_router(x, w, b, top_k=2)
    s = 1 / (1 + np.exp(-(x.astype('float64') @ w)))
    chosen = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # told apart: a large epsilon moves every weight
    _, far = moe_router(x, w, b, top_k=2, norm_epsilon=0.5)
    assert (np.asarray(far) < 0.9 * np.asarray(plain)).all()


# -- analysis rules --------------------------------------------------------------

def _infer_and_cost(op_type, inputs, in_slots, out_slots, attrs):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.analysis.cost import op_cost
    from paddle_tpu.analysis.infer import VarInfo, infer_op
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        blk = main.global_block()
        env = {}
        for name, (shape, dtype) in inputs.items():
            blk.create_var(name=name, shape=shape, dtype=dtype)
            env[name] = VarInfo(shape, dtype)
        op = blk.append_op(op_type, inputs=in_slots,
                           outputs={s: [s.lower()] for s in out_slots},
                           attrs=attrs)
        out = infer_op(op, env, blk)
        for slot in out_slots:
            env[slot.lower()] = out[slot]
        return out, op_cost(op, env, blk)


T, S = 16, 4
RULES = {
    'short_conv_prefill': (
        dict(x=((1, T, 3 * H), 'bfloat16'), w=((3, H), 'bfloat16'),
             n=((), 'int32')),
        dict(x=['x'], w=['w'], last=['n']),
        {'Out': ((1, T, H), 'bfloat16'),
         'State': ((1, 1, 2, H), 'float32')}, T * H * 7),
    'short_conv_step': (
        dict(x=((S, 1, 3 * H), 'bfloat16'), w=((3, H), 'bfloat16'),
             s=((S + 1, 1, 2, H), 'float32'), r=((S,), 'int32')),
        dict(x=['x'], w=['w'], state=['s'], rows=['r']),
        {'Out': ((S, 1, H), 'bfloat16'),
         'State': ((S + 1, 1, 2, H), 'float32')}, S * H * 7),
}


@pytest.mark.parametrize('op_type', sorted(RULES))
def test_both_ops_have_an_infer_rule_and_a_cost_rule(op_type):
    from paddle_tpu.analysis import has_cost_rule
    from paddle_tpu.analysis.infer import has_rule
    from paddle_tpu.ops.registry import get_op
    inputs, in_slots, outs, flops = RULES[op_type]
    assert has_rule(op_type) and has_cost_rule(op_type)
    assert set(in_slots) == set(get_op(op_type).input_slots)
    out, cost = _infer_and_cost(op_type, inputs, in_slots, list(outs), {})
    for slot, (shape, dtype) in outs.items():
        assert tuple(out[slot].shape) == shape and out[slot].dtype == dtype
    assert cost.flops == flops
    # the rule and the kernel agree on shapes and dtypes
    rng = np.random.RandomState(0)
    args = []
    for slot in get_op(op_type).input_slots:
        shape, dtype = inputs[in_slots[slot][0]]
        args.append(np.zeros(shape, dtype) if dtype.startswith('int')
                    else jnp.asarray(rng.randn(*shape), dtype))
    for value, (shape, dtype) in zip(get_op(op_type).fn(*args),
                                     outs.values()):
        assert value.shape == shape and str(value.dtype) == dtype


@pytest.mark.parametrize('op_type,change,match', [
    ('short_conv_prefill', dict(x=((1, T, 2 * H), 'bfloat16')), 'B | C | z'),
    ('short_conv_prefill', dict(w=((1, H), 'bfloat16')), 'no state'),
    ('short_conv_step', dict(s=((S + 1, 1, 3, H), 'float32')), 'block'),
    ('short_conv_step', dict(r=((S + 1,), 'int32')), 'rows')])
def test_infer_rules_refuse_shapes_that_cannot_agree(op_type, change, match):
    from paddle_tpu.analysis.infer import InferError
    inputs, in_slots, outs, _ = RULES[op_type]
    with pytest.raises(InferError, match=match):
        _infer_and_cost(op_type, dict(inputs, **change), in_slots,
                        list(outs), {})
