"""Op-level contract of ops/llm_ops.py's power retention: the chunked scan
of a prefill and the per-slot update-and-read of a step, each against a
float64 numpy walk of the recurrence

    S_t = γ_t S_{t−1} + φ(k_t) [v_t, 1]ᵀ,   y_t = φ(q_t)ᵀ S_t[:, :d] / φ(q_t)ᵀ S_t[:, d]

with φ written here from index pairs (the op forms it from lane rolls), and
against the quadratic form with the cumulative gates."""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.llm_ops import (_phi, _state_parts,
                                    power_retention_prefill,
                                    power_retention_step, retention_gate,
                                    retention_state_forms,
                                    retention_state_rows)

H, G, D = 6, 2, 8          # 3 query heads read one key/value head
REP = H // G
BIG = D * (D + 1) // 2


def phi_pairs(x):
    """x_a² and √2·x_a·x_b (a < b), float64, in any fixed order."""
    a, b = np.triu_indices(x.shape[-1])
    coef = np.where(a == b, 1.0, np.sqrt(2.0))
    return coef * x[..., a] * x[..., b]


def draw(rng, length):
    q = rng.standard_normal((length, H, D)).astype('float32')
    k = rng.standard_normal((length, G, D)).astype('float32')
    v = rng.standard_normal((length, G, D)).astype('float32')
    # gates near 1, as a trained model's: an old state matters
    log_gate = np.log(1 / (1 + np.exp(-(rng.standard_normal(
        (length, G)) + 3.0)))).astype('float32')
    return q, k, v, log_gate


def walk(q, k, v, log_gate, state=None):
    """The recurrence a token at a time in float64: outputs (L, H, D) and
    the final (G, φ, D + 1) state."""
    q, k, v, gamma = (np.asarray(x, 'float64') for x in
                      (q, k, v, np.exp(log_gate.astype('float64'))))
    state = np.zeros((G, BIG, D + 1)) if state is None else state.copy()
    out = np.zeros((len(q), H, D))
    for t in range(len(q)):
        for g in range(G):
            state[g] = gamma[t, g] * state[g] + np.outer(
                phi_pairs(k[t, g]), np.append(v[t, g], 1.0))
        for j in range(H):
            read = phi_pairs(q[t, j]) @ state[j // REP]
            out[t, j] = read[:D] / read[D]
    return out, state


def quadratic(q, k, v, log_gate):
    q, k, v = (np.asarray(x, 'float64') for x in (q, k, v))
    b = np.cumsum(log_gate.astype('float64'), 0)            # (L, G)
    out = np.zeros((len(q), H, D))
    for j in range(H):
        g = j // REP
        a = np.square(q[:, j] @ k[:, g].T) * np.exp(b[:, None, g]
                                                    - b[None, :, g])
        a = np.tril(a)
        out[:, j] = (a @ v[:, g]) / a.sum(-1, keepdims=True)
    return out


def as_walked(block):
    """A (G, P, d) block as the walk's inner products see it: φ is a
    different order of the same pairs, so compare S through φ(x)ᵀ S φ-free:
    the block's S and z contracted with the op's own φ of probe vectors."""
    s, z = _state_parts(jnp.asarray(block))
    return np.asarray(s, 'float64'), np.asarray(z, 'float64')


def test_phi_is_the_symmetric_square_and_the_block_is_compact():
    rng = np.random.default_rng(0)
    x, y = rng.standard_normal((2, 5, 128)).astype('float32')
    assert _phi(x).shape == (5, 128 * 129 // 2)
    np.testing.assert_allclose((_phi(x) * _phi(y)).sum(-1),
                               np.square((x * y).sum(-1)), rtol=2e-4)
    np.testing.assert_allclose(
        np.sort(np.asarray(_phi(x[:, :D]), 'float64'), -1),
        np.sort(phi_pairs(x[:, :D].astype('float64')), -1), rtol=1e-6)
    # 8,256 rows of S, 65 of z, rounded to the 8 sublanes: 128 lanes wide,
    # within 0.1% of D x (d + 1) values
    assert retention_state_rows(128) == (8256, 65, 8328)
    assert 8328 * 128 / (8256 * 129) < 1.001
    with pytest.raises(ValueError):
        retention_state_rows(7)


@pytest.mark.parametrize('length,chunk,last', [
    (12, 4, 11), (12, 4, 6), (13, 4, 12), (16, 16, 9), (7, 256, 6),
    (16, 4, 0), (44, 4, 43), (44, 8, 20), (9, 2, 8), (5, 1, 4)])
def test_prefill_scan_is_the_recurrence(length, chunk, last):
    rng = np.random.default_rng([length, chunk, last])
    q, k, v, log_gate = draw(rng, length)
    out, state = power_retention_prefill(
        q[None], k[None], v[None], log_gate[None], np.int32(last),
        chunk=chunk)
    live = last + 1
    want, want_state = walk(q[:live], k[:live], v[:live], log_gate[:live])
    np.testing.assert_allclose(
        np.asarray(out)[0, :live].reshape(live, H, D), want, rtol=2e-4,
        atol=2e-5)
    np.testing.assert_allclose(want, quadratic(
        q[:live], k[:live], v[:live], log_gate[:live]), rtol=1e-9)
    # the state through probes: φ(x)ᵀ S and φ(x)·z in both orders of pairs
    s, z = as_walked(np.asarray(state)[0])
    probe = rng.standard_normal((3, D))
    got = np.einsum('pD,gDd->gpd', np.asarray(_phi(probe), 'float64'), s)
    np.testing.assert_allclose(
        got, np.einsum('pD,gDd->gpd', phi_pairs(probe),
                       want_state[..., :D]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.einsum('pD,gD->gp', np.asarray(_phi(probe), 'float64'), z),
        np.einsum('pD,gD->gp', phi_pairs(probe), want_state[..., D]),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize('chunk', [4, 12])
def test_padding_past_last_changes_nothing_whatever_it_holds(chunk):
    rng = np.random.default_rng(5)
    q, k, v, log_gate = draw(rng, 12)
    base = power_retention_prefill(q[None], k[None], v[None],
                                   log_gate[None], np.int32(6), chunk=chunk)
    k2, v2, g2 = k.copy(), v.copy(), log_gate.copy()
    k2[7:], v2[7:], g2[7:] = np.nan, 1e30, -50.0
    other = power_retention_prefill(q[None], k2[None], v2[None], g2[None],
                                    np.int32(6), chunk=chunk)
    np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(other[1]))
    np.testing.assert_array_equal(np.asarray(base[0])[0, :7],
                                  np.asarray(other[0])[0, :7])


@pytest.mark.parametrize('length,chunk', [(12, 4), (9, 256)])
def test_state_forms_are_the_sums_of_outer_products(length, chunk):
    """The block unpacked entry by entry: M[a, b] = Σ_i decay_i k_a k_b
    [v, 1], written here with no φ at all."""
    rng = np.random.default_rng([length, chunk])
    q, k, v, log_gate = draw(rng, length)
    _, state = power_retention_prefill(q[None], k[None], v[None],
                                       log_gate[None], chunk=chunk)
    forms = np.asarray(retention_state_forms(state))[0]
    assert forms.shape == (G, D, D, D + 1)
    k64, v1 = k.astype('float64'), np.concatenate(
        [v, np.ones((length, G, 1))], -1).astype('float64')
    cum = np.cumsum(log_gate.astype('float64'), 0)
    want = np.einsum('ig,iga,igb,igc->gabc', np.exp(cum[-1] - cum), k64,
                     k64, v1)
    np.testing.assert_allclose(forms, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(forms, forms.transpose(0, 2, 1, 3))


def test_state_forms_at_the_published_head_size():
    rng = np.random.default_rng(11)
    q = rng.standard_normal((1, 3, 5, 128)).astype('float32')
    k = rng.standard_normal((1, 3, 1, 128)).astype('float32')
    v = rng.standard_normal((1, 3, 1, 128)).astype('float32')
    log_gate = np.full((1, 3, 1), -0.01, 'float32')
    _, state = power_retention_prefill(q, k, v, log_gate)
    assert state.shape == (1, 1, 8328, 128)
    forms = np.asarray(retention_state_forms(state))[0, 0]
    v1 = np.concatenate([v, np.ones((1, 3, 1, 1))], -1)[0, :, 0]
    want = np.einsum('i,ia,ib,ic->abc', np.exp(-0.01 * np.arange(3)[::-1]),
                     k[0, :, 0].astype('float64'), k[0, :, 0], v1)
    np.testing.assert_allclose(forms, want, rtol=2e-5, atol=2e-5)


def test_whole_sequences_in_a_batch_with_no_last():
    rng = np.random.default_rng(6)
    a, b = draw(rng, 9), draw(rng, 9)
    out, _ = power_retention_prefill(*(np.stack(p) for p in zip(a, b)),
                                     chunk=4)
    for i, one in enumerate((a, b)):
        np.testing.assert_allclose(np.asarray(out)[i].reshape(9, H, D),
                                   walk(*one)[0], rtol=2e-4, atol=2e-5)


def test_step_advances_each_slots_row_and_idle_slots_the_scratch_row():
    """Prefill two prompts into rows 2 and 1, then step them in slots 0 and
    2 of four (slots 1 and 3 idle, on row 0) three times: every output is
    the walk's next row, the rows of other requests are untouched."""
    rng = np.random.default_rng(7)
    first, second = draw(rng, 5), draw(rng, 9)
    more = [draw(rng, 3), draw(rng, 3)]
    _, _, prow = retention_state_rows(D)
    state = np.zeros((4, G, prow, D), 'float32')       # rows 0..3
    for row, prompt in ((2, first), (1, second)):
        _, made = power_retention_prefill(*(x[None] for x in prompt),
                                          np.int32(len(prompt[0]) - 1),
                                          chunk=4)
        state[row] = np.asarray(made)[0]
    state[3] = 7.0                                     # another request's
    rows = np.asarray([2, 0, 1, 0], np.int32)
    idle = draw(rng, 1)
    for t in range(3):
        feed = [np.stack([more[0][i][t], idle[i][0], more[1][i][t],
                          idle[i][0]])[:, None] for i in range(4)]
        out, state = power_retention_step(*feed, state, rows)
        state = np.asarray(state)
        for slot, (prompt, extra) in ((0, (first, more[0])),
                                      (2, (second, more[1]))):
            whole = [np.concatenate([p, e[:t + 1]])
                     for p, e in zip(prompt, extra)]
            np.testing.assert_allclose(
                np.asarray(out)[slot, 0].reshape(H, D), walk(*whole)[0][-1],
                rtol=3e-4, atol=3e-5)
        assert (state[3] == 7.0).all()
    assert np.abs(state[0]).max() > 0                  # the scratch row


def test_gate_is_a_float32_log_sigmoid_with_its_shift():
    rng = np.random.default_rng(8)
    x = rng.standard_normal((2, 3, 16)).astype('float32')
    w = rng.standard_normal((16, G)).astype('float32')
    got = retention_gate(jnp.asarray(x, jnp.bfloat16),
                         jnp.asarray(w, jnp.bfloat16), shift=6.0)
    assert got.dtype == jnp.float32 and got.shape == (2, 3, G)
    xb = np.asarray(jnp.asarray(x, jnp.bfloat16), 'float64')
    wb = np.asarray(jnp.asarray(w, jnp.bfloat16), 'float64')
    np.testing.assert_allclose(
        got, -np.log1p(np.exp(-(xb @ wb + 6.0))), rtol=1e-4, atol=1e-6)
