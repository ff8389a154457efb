"""The gated delta rule's two forms (``tpudist/ops/delta_rule.py``) against
the recurrence written out token by token in float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import delta_rule as dr


def recurrence(q, k, v, g, beta, state):
    """Token by token, float64: ``q``, ``k`` ``[B, T, H, dk]``, ``v`` ``[B,
    T, H, dv]``, ``g``, ``beta`` ``[B, T, H]``, ``state`` ``[B, H, dk,
    dv]``."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    s = np.array(state, np.float64)
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t])[..., None, None]
        seen = np.einsum("bhkv,bhk->bhv", s, k[:, t])
        delta = (v[:, t] - seen) * beta[:, t][..., None]
        s = s + np.einsum("bhk,bhv->bhkv", k[:, t], delta)
        out[:, t] = np.einsum("bhkv,bhk->bhv", s, q[:, t])
    return out, s


def draw(seed, b, t, h, dk, dv, carried=True):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    # keys with a common part, as SiLU's positive mean gives them: the
    # triangular system is then far from the identity
    k = unit(rng.normal(size=(b, t, h, dk)) + 0.8)
    q = unit(rng.normal(size=(b, t, h, dk))) * dk ** -0.5
    v = rng.normal(size=(b, t, h, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(b, t, h)))
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(b, t, h)) * 2))
    state = (rng.normal(size=(b, h, dk, dv)) if carried
             else np.zeros((b, h, dk, dv)))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


@pytest.mark.parametrize("t,carried", [(64, False), (128, True), (100, True),
                                       (7, True), (192, False)])
def test_chunk_matches_recurrence(t, carried):
    args = draw(t, 2, t, 3, 16, 24, carried)
    want_o, want_s = recurrence(*args)
    got_o, got_s = jax.jit(dr.gated_delta_chunk)(*args)
    np.testing.assert_allclose(got_o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)


def test_chunk_carries_state_across_calls():
    """Two chunks in turn equal one of their joint length."""
    args = draw(11, 1, 160, 2, 16, 32)
    q, k, v, g, beta, s0 = args
    whole_o, whole_s = dr.gated_delta_chunk(*args)
    cut = 96
    o1, s1 = dr.gated_delta_chunk(q[:, :cut], k[:, :cut], v[:, :cut],
                                  g[:, :cut], beta[:, :cut], s0)
    o2, s2 = dr.gated_delta_chunk(q[:, cut:], k[:, cut:], v[:, cut:],
                                  g[:, cut:], beta[:, cut:], s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), whole_o,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s2, whole_s, atol=1e-4, rtol=1e-4)


def test_chunk_rows_not_valid_leave_the_state():
    """The padded rows of a last chunk: the state after them is the state
    after the valid rows, bit for bit the same as without them."""
    q, k, v, g, beta, s0 = draw(5, 1, 128, 2, 16, 32)
    n = 70
    valid = (jnp.arange(128) < n)[None]
    o, s = dr.gated_delta_chunk(q, k, v, g, beta, s0, valid)
    want_o, want_s = recurrence(q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                beta[:, :n], s0)
    np.testing.assert_allclose(o[:, :n], want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4, rtol=2e-4)
    # a sub-block of nothing but padding does not touch it at all
    _, s_short = dr.gated_delta_chunk(
        q[:, :64], k[:, :64], v[:, :64], g[:, :64], beta[:, :64], s0,
        (jnp.arange(64) < 0)[None])
    np.testing.assert_array_equal(s_short, s0)


@pytest.mark.parametrize("h,dk,dv", [(4, 16, 32), (2, 8, 192), (3, 8, 16),
                                     (2, 16, 128)])
def test_step_matches_recurrence(h, dk, dv):
    q, k, v, g, beta, s0 = draw(h * dv, 3, 1, h, dk, dv)
    want_o, want_s = recurrence(q, k, v, g, beta, s0)
    o, s = dr.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0], dr.state_from_heads(s0))
    np.testing.assert_allclose(o, want_o[:, 0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dr.state_to_heads(s, h), want_s, atol=1e-5,
                               rtol=1e-5)


def test_step_lane_not_valid_keeps_its_state_bitwise():
    q, k, v, g, beta, s0 = draw(3, 4, 1, 4, 16, 32)
    flat = dr.state_from_heads(s0)
    valid = jnp.asarray([True, False, True, False])
    _, s = dr.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0], flat, valid)
    _, moved = dr.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0], flat)
    np.testing.assert_array_equal(s[1], flat[1])
    np.testing.assert_array_equal(s[3], flat[3])
    np.testing.assert_array_equal(s[0], moved[0])
    assert not np.array_equal(moved[1], flat[1])


def test_steps_in_turn_equal_a_chunk():
    q, k, v, g, beta, s0 = draw(9, 2, 12, 2, 16, 32)
    want_o, want_s = dr.gated_delta_chunk(q, k, v, g, beta, s0)
    s = dr.state_from_heads(s0)
    outs = []
    for t in range(12):
        o, s = dr.gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(dr.state_to_heads(s, 2), want_s, atol=1e-4,
                               rtol=1e-4)


def test_state_layouts_round_trip():
    s = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    flat = dr.state_from_heads(s)
    assert flat.shape == (2, 4, 15)
    np.testing.assert_array_equal(dr.state_to_heads(flat, 3), s)
    # head h's columns are [h * dv, (h + 1) * dv)
    np.testing.assert_array_equal(flat[:, :, 5:10], s[:, 1])


def test_step_heads_keeps_slabs_whole():
    assert dr.step_heads(30, 96, 192) == (10, 2)
    assert dr.step_heads(4, 16, 32) == (4, 4)
    assert dr.step_heads(3, 8, 16) == (3, 3)
    assert dr.step_heads(8, 128, 128) == (8, 1)
