"""Op-level contract of ops/llm_ops.py's Mamba-2 mixer: the chunked (SSD)
prefill and the one-token step, each against a float64 numpy walk of the
recurrence a row at a time

    S_t,h = exp(Δ_t,h A_h) S_{t-1},h + Δ_t,h x_t,h ⊗ B_t,g
    y_t,h = S_t,h C_t,g + D_h x_t,h

over the causal filter of the rows; the state a prefill leaves is the
prompt's TRUE end's (rows past `last` enter with Δ = 0), the step's rows
are the slots' own and the scratch row's, the analysis rules; and the
experts' relu² form of `moe_experts` (the pallas kernel in interpret mode
and the XLA path) against plain products, with the gated silu form as it
was."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import llm_ops, pallas_moe
from paddle_tpu.ops.llm_ops import (moe_experts, ssm_scan_prefill,
                                    ssm_scan_step, ssm_state_parts,
                                    ssm_state_width)

# a hidden width, heads H of P over G groups of N, a filter of K taps
HID, H, P, G, N, K = 16, 4, 3, 2, 5, 4
D_INNER = H * P
CONV = D_INNER + 2 * G * N
ATTRS = {'head_dim': P, 'groups': G}


def weights(rng):
    f32 = lambda a: np.asarray(a, np.float32)
    return (f32(rng.standard_normal((HID, D_INNER + CONV + H)) * 0.3),
            f32(rng.standard_normal((K, CONV)) * 0.5),
            f32(rng.standard_normal(CONV) * 0.1),
            f32(rng.standard_normal(H) * 0.5 - 1.0),
            f32(np.log(rng.uniform(1, 16, H))),
            f32(rng.standard_normal(H)),
            f32(1 + 0.1 * rng.standard_normal(D_INNER)))


def walk(x, ws, last=None, s=None, window=None):
    """One sequence x (T, h) a row at a time in float64: (the mixer's rows
    before W_out (T, H·P), S (H, P, N) after row ``last``, the raw xBC rows
    last - 2 .. last)."""
    w_in, cw, cb, dtb, alog, d, nw = (np.asarray(a, 'float64') for a in ws)
    proj = np.asarray(x, 'float64') @ w_in
    z, raw = proj[:, :D_INNER], proj[:, D_INNER:D_INNER + CONV]
    dt = proj[:, D_INNER + CONV:] + dtb
    last = len(x) - 1 if last is None else last
    s = np.zeros((H, P, N)) if s is None else s.copy()
    window = np.zeros((K - 1, CONV)) if window is None else window.copy()
    a = -np.exp(alog)
    out = []
    for t in range(len(x)):
        full = np.concatenate([window, raw[t][None]])
        c = (cw * full).sum(0) + cb
        xbc = c / (1 + np.exp(-c))
        xs = xbc[:D_INNER].reshape(H, P)
        b = xbc[D_INNER:D_INNER + G * N].reshape(G, N)
        cc = xbc[D_INNER + G * N:].reshape(G, N)
        delta = np.log1p(np.exp(dt[t])) if t <= last else np.zeros(H)
        for h in range(H):
            g = h // (H // G)
            s[h] = np.exp(delta[h] * a[h]) * s[h] \
                + delta[h] * np.outer(xs[h], b[g])
        y = np.stack([s[h] @ cc[h // (H // G)] for h in range(H)]) \
            + d[:, None] * xs
        gated = (y.reshape(-1) * z[t] / (1 + np.exp(-z[t]))).reshape(G, -1)
        out.append((gated / np.sqrt((gated ** 2).mean(-1, keepdims=True)
                                    + 1e-5)).reshape(-1) * nw)
        if t <= last:
            window = full[1:]
    return np.stack(out), s, window


def parts(block):
    return ssm_state_parts(block, P, CONV, K)


@pytest.mark.parametrize('chunk', [4, 128])
@pytest.mark.parametrize('length', [1, 5, 13])
def test_the_chunked_prefill_equals_the_recurrence(length, chunk):
    """Lengths that are no multiple of the chunk, one chunk and several."""
    rng = np.random.default_rng([length, chunk])
    ws = weights(rng)
    x = rng.standard_normal((2, length, HID)).astype('float32')
    out, state = ssm_scan_prefill(x, *ws, chunk=chunk, **ATTRS)
    cells, width = ssm_state_width(H, P, N, CONV, K)
    assert out.shape == (2, length, D_INNER)
    assert state.shape == (2, cells + width, H) and state.dtype == jnp.float32
    for b in range(2):
        want, s, window = walk(x[b], ws)
        np.testing.assert_allclose(out[b], want, rtol=2e-5, atol=2e-5)
        got_s, got_window = parts(state[b])
        np.testing.assert_allclose(got_s, s, rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(got_window, window, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('last', [0, 2, 3, 6, 7])
def test_rows_past_last_leave_the_state_as_it_stood(last):
    """A prompt of last + 1 rows padded to a rung of 8 with rows of any
    value: its rows and the state are what the unpadded prompt gives."""
    rng = np.random.default_rng(last)
    ws = weights(rng)
    x = rng.standard_normal((1, 8, HID)).astype('float32') * 3
    out, state = ssm_scan_prefill(x, *ws, np.int32(last), chunk=4, **ATTRS)
    alone, kept = ssm_scan_prefill(x[:, :last + 1], *ws, chunk=4, **ATTRS)
    np.testing.assert_allclose(out[:, :last + 1], alone, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(state, kept, rtol=1e-5, atol=1e-7)
    if last < 7:
        _, rung = ssm_scan_prefill(x, *ws, chunk=4, **ATTRS)
        assert np.abs(parts(rung)[0] - parts(state)[0]).max() > 1e-3


@pytest.mark.parametrize('slots', [3, 20])
def test_the_step_advances_each_slots_row_and_the_scratch_row_alone(slots):
    """A step over ``slots`` slots (20: two groups of the walk) from states a
    prefill left: each live slot's row and result are the recurrence's next
    row; idle slots (row 0) leave every other row as it was."""
    rng = np.random.default_rng(slots)
    ws = weights(rng)
    prompts = rng.standard_normal((slots, 6, HID)).astype('float32')
    _, kept = ssm_scan_prefill(prompts, *ws, chunk=4, **ATTRS)
    state = jnp.zeros((slots + 2,) + kept.shape[1:]).at[2:].set(kept)
    live = np.arange(slots) % 3 != 1
    rows = np.where(live, np.arange(slots) + 2, 0).astype(np.int32)
    x = rng.standard_normal((slots, 1, HID)).astype('float32')
    out, after = ssm_scan_step(x, *ws, state, rows, **ATTRS)
    assert out.shape == (slots, 1, D_INNER)
    for i in np.flatnonzero(live):
        want, s, window = walk(np.concatenate([prompts[i], x[i]]), ws)
        np.testing.assert_allclose(out[i, 0], want[-1], rtol=2e-5, atol=2e-5)
        got_s, got_window = parts(after[i + 2])
        np.testing.assert_allclose(got_s, s, rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(got_window, window, rtol=1e-5, atol=1e-6)
    for i in np.flatnonzero(~live):
        np.testing.assert_array_equal(after[i + 2], state[i + 2])
    assert not np.asarray(after[1]).any()


def test_the_projection_and_state_are_float32_whatever_the_rows_are():
    """bf16 rows and weights: W_in's product is kept in float32 (xBC and dt
    are what the state is made of), the state is float32, the rows return
    in bf16."""
    rng = np.random.default_rng(9)
    ws = [jnp.asarray(w, jnp.bfloat16) for w in weights(rng)]
    ws[3:6] = [jnp.asarray(w, jnp.float32) for w in ws[3:6]]
    x = jnp.asarray(rng.standard_normal((1, 5, HID)), jnp.bfloat16)
    out, state = ssm_scan_prefill(x, *ws, chunk=4, **ATTRS)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    _, _, window = walk(np.asarray(x[0], np.float32),
                        [np.asarray(w, np.float32) for w in ws])
    # the window is the float32 product of the bf16 operands, unrounded
    np.testing.assert_allclose(parts(state[0])[1], window, rtol=1e-6,
                               atol=1e-6)


# -- the rules ---------------------------------------------------------------

S, T = 4, 8


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


WEIGHTS = dict(w=((HID, D_INNER + CONV + H), 'bfloat16'),
               cw=((K, CONV), 'bfloat16'), cb=((CONV,), 'bfloat16'),
               dtb=((H,), 'float32'), al=((H,), 'float32'),
               d=((H,), 'float32'), nw=((D_INNER,), 'bfloat16'))
SLOTS = dict(w_in=['w'], conv_w=['cw'], conv_b=['cb'], dt_bias=['dtb'],
             a_log=['al'], d=['d'], norm_w=['nw'])
BLOCK = (sum(ssm_state_width(H, P, N, CONV, K)), H)
RULES = {
    'ssm_scan_prefill': (
        dict(WEIGHTS, x=((1, T, HID), 'bfloat16'), n=((), 'int32')),
        dict(SLOTS, x=['x'], last=['n']),
        {'Out': ((1, T, D_INNER), 'bfloat16'),
         'State': ((1,) + BLOCK, 'float32')}),
    'ssm_scan_step': (
        dict(WEIGHTS, x=((S, 1, HID), 'bfloat16'),
             s=((S + 1,) + BLOCK, 'float32'), r=((S,), 'int32')),
        dict(SLOTS, x=['x'], state=['s'], rows=['r']),
        {'Out': ((S, 1, D_INNER), 'bfloat16'),
         'State': ((S + 1,) + BLOCK, 'float32')}),
}


@pytest.mark.parametrize('op_type', sorted(RULES))
def test_both_ops_have_an_infer_rule_and_a_cost_rule(op_type):
    from paddle_tpu.analysis import has_cost_rule
    from paddle_tpu.analysis.infer import has_rule
    from paddle_tpu.ops.registry import get_op
    inputs, in_slots, outs = RULES[op_type]
    assert has_rule(op_type) and has_cost_rule(op_type)
    assert set(in_slots) == set(get_op(op_type).input_slots)
    out, cost = _infer_and_cost(op_type, inputs, in_slots, list(outs),
                                ATTRS)
    for slot, (shape, dtype) in outs.items():
        assert tuple(out[slot].shape) == shape and out[slot].dtype == dtype
    rows = T if op_type == 'ssm_scan_prefill' else S
    # at least W_in's 2·h·width FLOPs a row
    assert cost.flops >= rows * 2 * HID * (D_INNER + CONV + H)
    # the rule and the kernel agree on shapes and dtypes
    rng = np.random.RandomState(0)
    args = []
    for slot in get_op(op_type).input_slots:
        shape, dtype = inputs[in_slots[slot][0]]
        args.append(np.zeros(shape, dtype) if dtype.startswith('int')
                    else jnp.asarray(rng.randn(*shape), dtype))
    for value, (shape, dtype) in zip(get_op(op_type).fn(*args, **ATTRS),
                                     outs.values()):
        assert value.shape == shape and str(value.dtype) == dtype


@pytest.mark.parametrize('op_type,change,match', [
    ('ssm_scan_prefill', dict(w=((HID, D_INNER + CONV), 'bfloat16')),
     'z | xBC | dt'),
    ('ssm_scan_prefill', dict(cw=((1, CONV), 'bfloat16')), 'no state'),
    ('ssm_scan_prefill', dict(cw=((K, CONV + 3), 'bfloat16')), 'channels'),
    ('ssm_scan_step', dict(s=((S + 1, 7, H), 'float32')), 'block'),
    ('ssm_scan_step', dict(r=((S + 1,), 'int32')), 'rows')])
def test_infer_rules_refuse_shapes_that_cannot_agree(op_type, change, match):
    from paddle_tpu.analysis.infer import InferError
    inputs, in_slots, outs = RULES[op_type]
    with pytest.raises(InferError, match=match):
        _infer_and_cost(op_type, dict(inputs, **change), in_slots,
                        list(outs), ATTRS)


# -- the experts' relu² form --------------------------------------------------

E, HE, F = 4, 32, 16


def _plain(x, ids, weights, ws, relu2, held=(0, E)):
    """Σ_k weights[t, k] · E_ids[t, k](x[t]) token by token in float64, the
    experts held alone."""
    x = np.asarray(x, 'float64')
    ws = [None if w is None else np.asarray(w, 'float64') for w in ws]
    gate, up, down = ws
    out = np.zeros_like(x)
    for t in range(len(x)):
        for e, w in zip(ids[t], weights[t]):
            e = e - held[0]
            if not 0 <= e < held[1]:
                continue
            u = x[t] @ up[e]
            hid = np.maximum(u, 0) ** 2 if relu2 \
                else (x[t] @ gate[e]) / (1 + np.exp(-(x[t] @ gate[e]))) * u
            out[t] += w * (hid @ down[e])
    return out


@pytest.mark.parametrize('held', [None, (2, E)])
@pytest.mark.parametrize('activation', ['silu', 'relu2'])
def test_the_op_equals_plain_products(activation, held):
    rng = np.random.default_rng(7)
    x = rng.standard_normal((9, HE)).astype('float32')
    width = E + (held[0] if held else 0)
    ids = np.stack([rng.permutation(width)[:2] for _ in range(9)]
                   ).astype(np.int32)
    weights = rng.uniform(0.2, 1.0, (9, 2)).astype('float32')
    gate, up, down = (rng.standard_normal(s).astype('float32') * 0.3
                      for s in ((E, HE, F), (E, HE, F), (E, F, HE)))
    if activation == 'relu2':
        gate = None
    attrs = {'activation': activation}
    if held:
        attrs['experts_held'] = held
    out, counts = moe_experts(x, ids, weights, gate, up, down, **attrs)
    want = _plain(x, ids, weights, (gate, up, down),
                  activation == 'relu2', held or (0, E))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    first = held[0] if held else 0
    assert counts.tolist() == [int(((ids - first) == e).sum())
                               for e in range(E)]


@pytest.mark.parametrize('activation', ['silu', 'relu2'])
def test_the_kernel_in_interpret_mode_equals_the_xla_path(activation,
                                                          monkeypatch):
    """`moe_experts` with the kernel's predicate holding (the pallas grouped
    matmul in interpret mode, the activation in its epilogue) against the
    same op's `lax.ragged_dot` path on the same inputs."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((40, HE)).astype('float32')
    ids = rng.integers(0, E, (40, 3)).astype(np.int32)
    weights = rng.uniform(0.2, 1.0, (40, 3)).astype('float32')
    gate, up, down = (rng.standard_normal(s).astype('float32') * 0.3
                      for s in ((E, HE, F), (E, HE, F), (E, F, HE)))
    if activation == 'relu2':
        gate = None
    xla, _ = moe_experts(x, ids, weights, gate, up, down,
                         activation=activation)
    interpret = pallas_moe.expert_ffn
    monkeypatch.setattr(llm_ops, 'experts_kernel_applies', lambda *a: True)
    monkeypatch.setattr(llm_ops, 'expert_ffn', lambda *a, **kw: interpret(
        *a, interpret=True, **kw))
    kernel, _ = moe_experts(x, ids, weights, gate, up, down,
                            activation=activation)
    np.testing.assert_allclose(kernel, xla, rtol=2e-5, atol=2e-5)


def test_the_activation_and_the_gate_must_agree():
    x = np.zeros((2, HE), np.float32)
    ids = np.zeros((2, 1), np.int32)
    w = np.ones((2, 1), np.float32)
    up = np.zeros((E, HE, F), np.float32)
    down = np.zeros((E, F, HE), np.float32)
    with pytest.raises(ValueError, match='relu2'):
        moe_experts(x, ids, w, up, up, down, activation='relu2')
    with pytest.raises(ValueError, match='silu'):
        moe_experts(x, ids, w, None, up, down)


def test_the_relu2_feed_forward_is_plain_products():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((5, HE)).astype('float32')
    up = rng.standard_normal((HE, F)).astype('float32')
    down = rng.standard_normal((F, HE)).astype('float32')
    got = jax.jit(llm_ops.relu2_ffn)(x, up, down)
    np.testing.assert_allclose(got, (np.maximum(x @ up, 0) ** 2) @ down,
                               rtol=1e-5, atol=1e-4)
