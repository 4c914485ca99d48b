"""``ops/power_retention.py`` on the CPU at a tiny size: ``phi``'s layout,
the XLA forms against the token-by-token recurrence and against the
attention form, and both kernels interpreted against their XLA forms."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_setup_brumby as setup
from benchmarks import reference_brumby as reference
from family_harness import rel as _rel
from vnsum_tpu.ops import power_retention as pr

EPS = 1e-6


def _case(seed=0, rows=3, S=20, H=4, KV=2, d=16, gates=(0.0, 6.0)):
    """q, k, v as a QK-norm leaves them and a gate a KV head whose
    pre-sigmoid lies in ``gates``; a stacked state of two layers."""
    k = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(k[0], (rows, S, H, d))
    kk = jax.random.normal(k[1], (rows, S, KV, d))
    v = jax.random.normal(k[2], (rows, S, KV, d))
    gamma = jax.nn.log_sigmoid(jax.random.uniform(
        k[3], (rows, S, KV), minval=gates[0], maxval=gates[1]))
    T = pr.n_tiles(d)
    state = jax.random.normal(k[4], (2, rows, KV, T, d, d))
    # a normaliser that could be one: Z = sum k k^T is positive semi-definite
    r = jax.random.normal(k[5], (2, rows, KV, d, 3))
    norm = jnp.einsum("lbhir,lbhjr->lbhij", r, r)
    return q, kk, v, gamma, state, norm


def _how(d=16):
    return dict(scale=d ** -0.5, eps=EPS)


# -- phi ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [2, 4, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_scaled_dot_product_squared(d):
    """The layout as built: T = d / 2 + 1 tiles of d lanes, the weights and
    the scale on the query side alone."""
    a, b = jax.random.normal(jax.random.key(d), (2, 5, d))
    s = d ** -0.5
    pq, pk = pr.phi_tiles(a, s), pr.phi_tiles(b)
    assert pq.shape == pk.shape == (5, d // 2 + 1, d)
    got = (np.asarray(pq, np.float64) * np.asarray(pk)).sum(axis=(-1, -2))
    want = np.asarray(jnp.sum(a * b, -1), np.float64) ** 2 * s * s
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * want.max())


def test_phi_holds_every_pair_once_and_the_far_pairs_twice():
    d = 16
    x = jnp.asarray(np.arange(1, d + 1, dtype=np.float32))
    tiles = np.asarray(pr.phi_tiles(x))
    assert tiles.shape == (9, 16)
    np.testing.assert_array_equal(tiles[0], np.arange(1, 17) ** 2)
    pairs = {}
    for r in range(1, 9):
        for i in range(d):
            pair = frozenset((i, (i - r) % d))
            assert tiles[r, i] == (i + 1) * ((i - r) % d + 1)
            pairs[pair] = pairs.get(pair, 0) + 1
    assert len(pairs) == d * (d - 1) // 2
    assert sorted(set(pairs.values())) == [1, 2]
    assert sum(n == 2 for n in pairs.values()) == d // 2   # distance d / 2
    np.testing.assert_array_equal(np.asarray(pr.tile_weights(d)),
                                  [1] + [2] * 7 + [1])
    with pytest.raises(ValueError, match="odd"):
        pr.n_tiles(15)


def test_the_reference_lays_the_same_state_in_its_own_order():
    """``engine_setup_brumby.as_the_reference_lays_it`` maps the program's
    tiles and its unpacked normaliser onto the reference's d (d + 1) / 2
    products: the same sums over tokens, and phi . phi the same square."""
    d = 16
    k = jax.random.normal(jax.random.key(1), (7, 2, d))
    v = jax.random.normal(jax.random.key(2), (7, 2, d))
    gamma = -jax.random.uniform(jax.random.key(3), (7, 2))
    S, z = reference.state_sums(k, v, gamma, keep=2)      # [2, KV, n, dv]
    q = jnp.zeros((1, 7, 2, d))
    _, St, Zt = pr.retention_recurrent_xla(
        q, k[None], v[None], gamma[None],
        jnp.zeros((1, 2, pr.n_tiles(d), d, d)), jnp.zeros((1, 2, d, d)),
        **_how())
    mine, mine_z = setup.as_the_reference_lays_it(St[0], Zt[0])
    assert mine.shape == (2, d * (d + 1) // 2, d)
    assert _rel(mine, S[-1]) < 1e-6 and _rel(mine_z, z[-1]) < 1e-6
    a, b = jax.random.normal(jax.random.key(4), (2, d))
    assert float(reference.phi(a) @ reference.phi(b)) == pytest.approx(
        float(a @ b) ** 2, rel=1e-5)
    # z in phi's layout is Z's entries at (i, i - r): phi_q . z = s^2 q^T Z q
    i = np.arange(d)
    packed = jnp.stack([Zt[0, 0][i, (i - r) % d]
                        for r in range(pr.n_tiles(d))])
    assert _rel(jnp.sum(pr.phi_tiles(a, 0.5) * packed),
                0.25 * a @ Zt[0, 0] @ a) < 1e-5


# -- the equations by hand -----------------------------------------------------------


@pytest.mark.parametrize("form", ["step", "chunked", "kernel"])
def test_decay_write_and_normaliser_on_a_two_token_row(form):
    """One head of two channels: w_11 = (s q1.k1)^2, w_21 = (s q2.k1)^2 g2,
    w_22 = (s q2.k2)^2; o_t = sum w v / (sum w + eps)."""
    q = np.asarray([[1.0, 2.0], [0.5, -1.0]])
    k = np.asarray([[2.0, 1.0], [-1.0, 3.0]])
    v = np.asarray([[1.0, -2.0], [4.0, 0.5]])
    gamma = np.log([0.9, 0.6])
    s = 2 ** -0.5
    w11 = (s * q[0] @ k[0]) ** 2
    w21 = (s * q[1] @ k[0]) ** 2 * 0.6
    w22 = (s * q[1] @ k[1]) ** 2
    want = [w11 * v[0] / (w11 + EPS),
            (w21 * v[0] + w22 * v[1]) / (w21 + w22 + EPS)]
    arrays = [jnp.asarray(a, jnp.float32).reshape(1, 2, 1, 2)
              for a in (q, k, v)]
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 2, 1)
    S0, Z0 = jnp.zeros((1, 1, 2, 2, 2)), jnp.zeros((1, 1, 2, 2))
    how = dict(scale=s, eps=EPS)
    if form == "step":
        o, S, Z = pr.retention_recurrent_xla(*arrays, g, S0, Z0, **how)
    elif form == "chunked":
        o, S, Z = pr.retention_chunked_xla(*arrays, g, S0, Z0, 8, **how)
    else:
        o, S, Z = pr.retention_prefill_scan(
            *arrays, g, S0[None], Z0[None], 0, jnp.zeros((1,), jnp.int32),
            chunk=8, interpret=True, **how)
        S, Z = S[0], Z[0]
    np.testing.assert_allclose(np.asarray(o)[0, :, 0], want, rtol=1e-5)
    # Z = g2 k1 k1^T + k2 k2^T; tile 0 of S the squares against v
    np.testing.assert_allclose(
        np.asarray(Z)[0, 0], 0.6 * np.outer(k[0], k[0]) + np.outer(k[1], k[1]),
        rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(S)[0, 0, 0],
        0.6 * np.outer(v[0], k[0] ** 2) + np.outer(v[1], k[1] ** 2), rtol=1e-5)


# -- the forms against each other ------------------------------------------------------


def _attention_form(q, k, v, gamma, scale):
    """[B, S, H, dv] by the reference's own head function."""
    B, S, H, d = q.shape
    G = H // k.shape[2]
    cum = jnp.cumsum(gamma, axis=1)
    return jnp.stack([jnp.stack([
        reference.retention_head(q[b, :, a], k[b, :, a // G], v[b, :, a // G],
                                 cum[b, :, a // G], gamma[b, :, a // G],
                                 scale, EPS)
        for a in range(H)], 1) for b in range(B)])


@pytest.mark.parametrize("S", [8, 20, 24])
def test_chunked_forms_equal_the_recurrence_with_a_state_coming_in(S):
    """``retention_chunked_xla`` and the interpreted
    ``retention_prefill_scan`` against one token at a time, state and
    normaliser non-zero, S a whole number of chunks and not; the other layer
    of the stacked state is not touched."""
    q, k, v, gamma, state, norm = _case(S=S)
    with jax.default_matmul_precision("highest"):
        want = pr.retention_recurrent_xla(q, k, v, gamma, state[1], norm[1],
                                          **_how())
        got = pr.retention_chunked_xla(q, k, v, gamma, state[1], norm[1], 8,
                                       **_how())
        ko, kS, kZ = pr.retention_prefill_scan(
            q, k, v, gamma, state, norm, 1, jnp.zeros((3,), jnp.int32),
            chunk=8, interpret=True, **_how())
    for mine, theirs in zip(got, want):
        assert _rel(mine, theirs) < 1e-5
    assert _rel(ko, want[0]) < 1e-5
    assert _rel(kS[1], want[1]) < 1e-5 and _rel(kZ[1], want[2]) < 1e-5
    np.testing.assert_array_equal(np.asarray(kS[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(kZ[0]), np.asarray(norm[0]))


@pytest.mark.parametrize("form", ["recurrent", "chunked", "kernel"])
def test_the_state_form_is_the_attention_form(form):
    """From a zero state every form gives the reference's [T, T] weights."""
    q, k, v, gamma, state, norm = _case(S=20)
    zero, zero_z = jnp.zeros_like(state), jnp.zeros_like(norm)
    with jax.default_matmul_precision("highest"):
        want = _attention_form(q, k, v, gamma, 0.25)
        if form == "recurrent":
            o, _, _ = pr.retention_recurrent_xla(q, k, v, gamma, zero[0],
                                                 zero_z[0], **_how())
        elif form == "chunked":
            o, _, _ = pr.retention_chunked_xla(q, k, v, gamma, zero[0],
                                               zero_z[0], 8, **_how())
        else:
            o, _, _ = pr.retention_prefill_scan(
                q, k, v, gamma, zero, zero_z, 0, jnp.zeros((3,), jnp.int32),
                chunk=8, interpret=True, **_how())
    assert _rel(o, want) < 1e-5


def test_five_query_heads_read_one_state():
    """GQA: a KV head's query heads read the state of that one head — the
    same as the KV head repeated for each of them."""
    q, k, v, gamma, _, _ = _case(H=10, KV=2, S=16)
    T = pr.n_tiles(16)
    zero = lambda kv: (jnp.zeros((3, kv, T, 16, 16)),  # noqa: E731
                       jnp.zeros((3, kv, 16, 16)))
    rep = lambda a: jnp.repeat(a, 5, axis=2)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        o, S, Z = pr.retention_chunked_xla(q, k, v, gamma, *zero(2), 8,
                                           **_how())
        o10, S10, _ = pr.retention_chunked_xla(
            q, rep(k), rep(v), rep(gamma), *zero(10), 8, **_how())
        ko, _, _ = pr.retention_prefill_scan(
            q, k, v, gamma, zero(2)[0][None], zero(2)[1][None], 0,
            jnp.zeros((3,), jnp.int32), chunk=8, interpret=True, **_how())
    assert _rel(o, o10) < 1e-6 and _rel(ko, o10) < 1e-5
    assert _rel(jnp.repeat(S, 5, axis=1), S10) < 1e-6


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_pads_leave_state_and_normaliser_exactly_zero_and_outputs_zero(form):
    """Rows with 0, 9 and 17 pads of 20 tokens in chunks of 8 (k = v = 0
    under the pad, whatever the gate reads there): pad positions read as
    exact zeros, the state after a row that is all pad is exactly zero, and
    what follows equals the recurrence over the row's real tokens alone."""
    q, k, v, gamma, state, norm = _case(S=20, rows=4)
    pads = jnp.asarray([0, 9, 17, 20])
    valid = (jnp.arange(20)[None, :] >= pads[:, None])[..., None, None]
    k, v = k * valid, v * valid
    zero, zero_z = jnp.zeros_like(state), jnp.zeros_like(norm)
    with jax.default_matmul_precision("highest"):
        want = pr.retention_recurrent_xla(q, k, v, gamma, zero[0], zero_z[0],
                                          **_how())
        if form == "kernel":
            o, S, Z = pr.retention_prefill_scan(
                q, k, v, gamma, zero, zero_z, 0, pads, chunk=8,
                interpret=True, **_how())
            S, Z = S[0], Z[0]
        else:
            o, S, Z = pr.retention_chunked_xla(q, k, v, gamma, zero[0],
                                               zero_z[0], 8, **_how())
    assert _rel(o, want[0]) < 1e-5 and _rel(S, want[1]) < 1e-5
    assert _rel(Z, want[2]) < 1e-5
    o = np.asarray(o)
    assert not o[1, :9].any() and not o[2, :17].any() and not o[3].any()
    assert not np.asarray(S)[3].any() and not np.asarray(Z)[3].any()
    # the all-pad row's 20 pads end inside the third chunk, which runs
    assert pr.retention_tokens_computed(np.asarray(pads), 20, 8) \
        == 24 + 16 + 8 + 8
    # a filler row of whole chunks computes nothing
    assert pr.retention_tokens_computed([24], 24, 8) == 0


@pytest.mark.parametrize("gate", [0.5, 0.9, 0.999, 0.99999])
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_no_exponent_overflows_at_any_gate_over_whole_chunks(form, gate):
    """A constant gate from 0.5 (a chunk of 32 decays by 2^-32) to 0.99999
    over several whole chunks: every exponent is a difference <= 0, nothing
    is Inf or NaN, and the error against the recurrence stays bounded."""
    q, k, v, _, state, norm = _case(S=96, rows=2)
    gamma = jnp.full((2, 96, 2), float(np.log(gate)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = pr.retention_recurrent_xla(q, k, v, gamma, state[1], norm[1],
                                          **_how())
        if form == "kernel":
            o, S, Z = pr.retention_prefill_scan(
                q, k, v, gamma, state, norm, 1, jnp.zeros((2,), jnp.int32),
                chunk=32, interpret=True, **_how())
            S, Z = S[1], Z[1]
        else:
            o, S, Z = pr.retention_chunked_xla(q, k, v, gamma, state[1],
                                               norm[1], 32, **_how())
    for a in (o, S, Z):
        assert np.isfinite(np.asarray(a)).all()
    assert _rel(o, want[0]) < 1e-4 and _rel(S, want[1]) < 1e-5
    assert _rel(Z, want[2]) < 1e-5


@pytest.mark.parametrize("rows", [[3, 0, 4, 1, 2], [4, 1], [2]],
                         ids=["permutation", "subset", "one-row"])
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_row_piece_writes_its_rows_of_the_state_and_no_other(form, rows):
    """``rows`` names, for each row of the inputs, the state's batch row it
    continues: those rows read what the same inputs give one row at a time,
    every other row — and the other layer of the stack — is bit-equal to
    what came in."""
    n = len(rows)
    q, k, v, gamma, _, _ = _case(seed=3, rows=n, S=20)
    _, _, _, _, state, norm = _case(seed=7, rows=5)
    idx = jnp.asarray(rows, jnp.int32)
    pads = jnp.asarray([0, 9, 17, 3, 8][:n], jnp.int32)
    valid = (jnp.arange(20)[None, :] >= pads[:, None])[..., None, None]
    k, v = k * valid, v * valid
    # a row behind a pad starts from zeros, as the engine's rows do
    fresh = (pads == 0)[:, None, None, None]
    state = state.at[:, idx].multiply(fresh[..., None])
    norm = norm.at[:, idx].multiply(fresh)
    with jax.default_matmul_precision("highest"):
        want = pr.retention_recurrent_xla(
            q, k, v, gamma, state[1][idx], norm[1][idx], **_how())
        if form == "kernel":
            o, S, Z = pr.retention_prefill_scan(
                q, k, v, gamma, state, norm, 1, pads, idx, chunk=8,
                interpret=True, **_how())
            np.testing.assert_array_equal(np.asarray(S[0]),
                                          np.asarray(state[0]))
            np.testing.assert_array_equal(np.asarray(Z[0]),
                                          np.asarray(norm[0]))
            S, Z = S[1], Z[1]
        else:
            o, S, Z = pr.retention_chunked_xla(
                q, k, v, gamma, state[1], norm[1], 8, idx, **_how())
    assert S.shape == state[1].shape and Z.shape == norm[1].shape
    # (an arbitrary state under the normaliser: a divisor may be small)
    assert _rel(o, want[0]) < 1e-4 and _rel(S[idx], want[1]) < 1e-5
    assert _rel(Z[idx], want[2]) < 1e-5
    others = [r for r in range(5) if r not in rows]
    np.testing.assert_array_equal(np.asarray(S)[others],
                                  np.asarray(state[1])[others])
    np.testing.assert_array_equal(np.asarray(Z)[others],
                                  np.asarray(norm[1])[others])


def test_the_state_and_the_normaliser_are_written_in_place():
    """Both kernels alias the stacked state and normaliser to their
    outputs; with ``rows`` they stand one operand later."""
    q, k, v, gamma, state, norm = _case(S=16)
    pads = jnp.zeros((3,), jnp.int32)

    def text(*rows):
        return str(jax.make_jaxpr(lambda *a: pr.retention_prefill_scan(
            *a, chunk=8, interpret=True, **_how()))(
                q, k, v, gamma, state, norm, 1, pads, *rows))

    whole, piece = text(), text(jnp.arange(3, dtype=jnp.int32))
    assert "input_output_aliases=((7, 1), (8, 2))" in whole
    assert "input_output_aliases=((8, 1), (9, 2))" in piece
    assert len(piece.split("\n")) == len(whole.split("\n"))
    step = str(jax.make_jaxpr(lambda *a: pr.retention_decode_update(
        *a, interpret=True, **_how()))(
            q[:, 0], k[:, 0], v[:, 0], gamma[:, 0], state, norm, 1))
    assert "input_output_aliases=((7, 1), (8, 2))" in step


@pytest.mark.parametrize("rows,H,KV,d", [
    (3, 4, 2, 16), (3, 10, 2, 16), (3, 2, 2, 16),
    # the cell's rows (map, reduce, one) and query heads a KV head 5 / 4 / 1
    (12, 10, 2, 16), (4, 8, 2, 16), (1, 2, 2, 16),
    # T = 7 tiles, a prime; T = 15, a product of 13 tiles and a rest of 2,
    # on an odd count of KV heads
    (3, 4, 2, 12), (2, 6, 3, 28)])
def test_decode_kernel_equals_its_xla_form_and_writes_one_layer(
        rows, H, KV, d):
    q, k, v, gamma, state, norm = _case(rows=rows, H=H, KV=KV, d=d)
    args = (q[:, 5], k[:, 5], v[:, 5], gamma[:, 5])
    want = pr.retention_step_xla(*args, state[1], norm[1], **_how(d))
    o, S, Z = pr.retention_decode_update(*args, state, norm, 1,
                                         interpret=True, **_how(d))
    assert o.shape == (rows, H, d)
    assert _rel(o, want[0]) < 1e-5 and _rel(S[1], want[1]) < 1e-6
    assert _rel(Z[1], want[2]) < 1e-6
    np.testing.assert_array_equal(np.asarray(S[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(Z[0]), np.asarray(norm[0]))


def test_two_layers_updates_in_a_row_leave_every_other_layer_as_it_was():
    """The stacked state of four layers through the update of layer 2 and
    then of layer 0, as the model's scan over layers hands it on: each
    layer's block is what its own call wrote, layers 1 and 3 are bit-equal
    to what came in, and the second call read nothing of the first's."""
    q, k, v, gamma, state, norm = _case(rows=2)
    state = jnp.concatenate([state, state[::-1] * 0.5], axis=0)
    norm = jnp.concatenate([norm, norm[::-1] * 0.5], axis=0)
    first = (q[:, 3], k[:, 3], v[:, 3], gamma[:, 3])
    second = (q[:, 4], k[:, 4], v[:, 4], gamma[:, 4])
    run = dict(interpret=True, **_how())
    o2, S, Z = pr.retention_decode_update(*first, state, norm, 2, **run)
    o0, S, Z = pr.retention_decode_update(*second, S, Z, 0, **run)
    for layer in (1, 3):
        np.testing.assert_array_equal(np.asarray(S[layer]),
                                      np.asarray(state[layer]))
        np.testing.assert_array_equal(np.asarray(Z[layer]),
                                      np.asarray(norm[layer]))
    for layer, args, o in ((2, first, o2), (0, second, o0)):
        want = pr.retention_step_xla(*args, state[layer], norm[layer],
                                     **_how())
        assert _rel(o, want[0]) < 1e-5
        assert _rel(S[layer], want[1]) < 1e-6
        assert _rel(Z[layer], want[2]) < 1e-6


def test_decode_steps_continue_a_prefill_as_one_longer_prefill():
    """A prefill of 16 tokens and then 4 one-token updates, both kernels
    interpreted, against one scan over the 20."""
    q, k, v, gamma, state, norm = _case(S=20)
    zero, zero_z = jnp.zeros_like(state), jnp.zeros_like(norm)
    pads = jnp.zeros((3,), jnp.int32)
    run = dict(interpret=True, **_how())
    with jax.default_matmul_precision("highest"):
        want = pr.retention_recurrent_xla(q, k, v, gamma, zero[0], zero_z[0],
                                          **_how())
        o, S, Z = pr.retention_prefill_scan(
            q[:, :16], k[:, :16], v[:, :16], gamma[:, :16], zero, zero_z, 0,
            pads, chunk=8, **run)
        outs = [o]
        for t in range(16, 20):
            o, S, Z = pr.retention_decode_update(
                q[:, t], k[:, t], v[:, t], gamma[:, t], S, Z, 0, **run)
            outs.append(o[:, None].astype(outs[0].dtype))
    assert _rel(jnp.concatenate(outs, 1), want[0]) < 1e-5
    assert _rel(S[0], want[1]) < 1e-5 and _rel(Z[0], want[2]) < 1e-5
