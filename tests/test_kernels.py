"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU) + hypothesis property tests;
then the hosting engine's DP recursion and its counter-keyed uniform
against independent references."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

ATTN_SHAPES = [
    # (B, Sq, Skv, Hq, Hkv, hd)
    (1, 16, 16, 1, 1, 16),
    (2, 64, 64, 4, 4, 32),
    (2, 128, 128, 4, 2, 64),      # GQA
    (1, 80, 80, 8, 1, 64),        # MQA, ragged seq (padding path)
    (1, 256, 256, 2, 2, 128),
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype):
    b, sq, skv, hq, hkv, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, hd), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 96, 4, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 96, 4, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 3), st.integers(1, 96), st.sampled_from([1, 2, 4]),
       st.sampled_from([16, 32, 64]), st.integers(0, 2 ** 31 - 1))
def test_flash_attention_property(b, s, h, hd, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)


SSD_SHAPES = [
    # (b, s, nh, dh, ng, ds, chunk)
    (1, 32, 2, 16, 1, 16, 16),
    (2, 64, 4, 32, 1, 32, 32),
    (1, 100, 4, 32, 2, 16, 32),    # ragged + grouped
    (2, 128, 8, 64, 1, 64, 64),
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(shape, dtype):
    b, s, nh, dh, ng, ds, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, nh, dh), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, ng, ds), dtype)
    C = jax.random.normal(ks[4], (b, s, ng, ds), dtype)
    y, hT = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    y_ref, hT_ref = ref.ssd_scan_ref(x, dt, A, B, C)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               rtol=1e-2, atol=1e-2)


def test_ssd_scan_with_initial_state():
    b, s, nh, dh, ng, ds = 1, 48, 2, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (b, s, nh, dh), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, ng, ds), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, ng, ds), jnp.float32)
    h0 = jax.random.normal(ks[5], (b, nh, dh, ds), jnp.float32)
    y, hT = ops.ssd_scan(x, dt, A, B, C, h0=h0, chunk=16)
    y_ref, hT_ref = ref.ssd_scan_ref(x, dt, A, B, C, h0=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), rtol=1e-3, atol=1e-3)


def test_ssd_scan_state_continuation():
    """Running two halves with state carry == running the whole sequence
    (the decode-from-prefill contract)."""
    b, s, nh, dh, ng, ds = 1, 64, 2, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(ks[0], (b, s, nh, dh), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, ng, ds), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, ng, ds), jnp.float32)
    y_full, hT_full = ops.ssd_scan(x, dt, A, B, C, chunk=16)
    h = s // 2
    y1, h1 = ops.ssd_scan(x[:, :h], dt[:, :h], A, B[:, :h], C[:, :h], chunk=16)
    y2, h2 = ops.ssd_scan(x[:, h:], dt[:, h:], A, B[:, h:], C[:, h:], h0=h1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hT_full), rtol=1e-3, atol=1e-3)


def test_model_attention_pallas_path_matches_xla():
    """The model-level attend() with impl=pallas agrees with xla_flash."""
    from repro.models.attention import attend
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
    a = attend(q, k, v, causal=True, impl="pallas")
    b = attend(q, k, v, causal=True, impl="xla_flash", chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# The hosting DP recursion: ``offline_opt.dp_fwd_chunk``, the lax.scan
# every fleet driver runs, against an independent float32 numpy replay.
# Bit-EXACT — array_equal, never allclose; tests/test_dp_rows_kernel.py
# holds the row-blocked kernel ``kernels.hosting.dp_minplus_rows`` to the
# same oracle.
# ----------------------------------------------------------------------

from repro.core.policies.offline_opt import (dp_fetch_matrix, dp_frontier0,
                                             dp_fwd_chunk)
from repro.core.scenarios.base import slot_uniform


def _dp_case(seed, chunk, K, k_used, T_len):
    """Random per-chunk DP inputs in dp_fwd_chunk's calling convention."""
    rng = np.random.default_rng(seed)
    lv32 = jnp.asarray(np.sort(rng.random(K)).astype(np.float32))
    fetch = dp_fetch_matrix(jnp.float32(rng.uniform(2, 8)), lv32)
    kmask = jnp.arange(K) < k_used
    cck = jnp.asarray(rng.uniform(0.1, 2.0, chunk).astype(np.float32))
    sck = jnp.asarray(rng.uniform(0.0, 3.0, (chunk, K)).astype(np.float32))
    tids = jnp.arange(chunk, dtype=jnp.int32)
    return (dp_frontier0(K), tids, cck, sck, lv32, kmask, fetch,
            jnp.asarray(T_len, jnp.int32))


def np_dp_holding(cck, sck, lv32, kmask):
    """The float32 per-slot holding costs ``[chunk, K]``: rent times level
    plus service, ``+inf`` on masked levels."""
    w = (np.asarray(cck)[:, None] * np.asarray(lv32)[None, :]
         + np.asarray(sck)).astype(np.float32)
    return np.where(np.asarray(kmask)[None, :], w, np.float32(np.inf))


def np_dp_forward(J0, tids, cck, sck, lv32, kmask, fetch, T_len):
    """Independent float32 numpy replay of one chunk of the recursion:
    first-occurrence argmin (np.argmin's documented tie rule), ``+inf`` on
    masked levels, and at slots ``t >= T_len`` a frozen frontier with
    identity argmins.  Returns ``(J', args [chunk, K])``."""
    w = np_dp_holding(cck, sck, lv32, kmask)
    fm = np.asarray(fetch)
    J, args = np.asarray(J0), []
    for i, t in enumerate(np.asarray(tids)):
        if t >= int(T_len):
            args.append(np.arange(J.shape[0]))
            continue
        trans = (J[:, None] + fm).astype(np.float32)
        args.append(trans.argmin(axis=0))
        J = (trans.min(axis=0) + w[i]).astype(np.float32)
    return J, np.stack(args)


DP_CASES = [
    # (chunk, K, k_used, T_len): aligned/odd chunks, +inf kmask pads,
    # frozen tails (T_len < chunk) and fully-frozen (T_len = 0)
    (16, 2, 2, 16),
    (8, 5, 5, 8),
    (37, 5, 3, 37),
    (37, 4, 4, 20),
    (64, 3, 2, 0),
    (1, 6, 4, 1),
]


def dp_case_seed(chunk, K):
    return chunk * 7 + K


@pytest.mark.parametrize("chunk,K,k_used,T_len", DP_CASES)
def test_dp_minplus_matches_xla_reference(chunk, K, k_used, T_len):
    """The scan against the numpy oracle on every case: masked levels
    and frozen tails included."""
    case = _dp_case(dp_case_seed(chunk, K), chunk, K, k_used, T_len)
    J, args = dp_fwd_chunk(*case)
    J_ref, args_ref = np_dp_forward(*case)
    assert np.array_equal(np.asarray(J), J_ref)
    assert np.array_equal(np.asarray(args), args_ref)


def test_dp_minplus_chained_chunks_match():
    """The frontier carried across chunk boundaries stays exact: two
    16-slot chunks == one 32-slot chunk == the oracle, J and args, with
    the horizon ending inside the second chunk."""
    (J0, _, cck, sck, lv32, kmask, fetch, _) = _dp_case(3, 32, 5, 4, 32)
    tids = jnp.arange(32, dtype=jnp.int32)
    Jx, ax = dp_fwd_chunk(J0, tids, cck, sck, lv32, kmask, fetch,
                          jnp.int32(27))
    J, parts = J0, []
    for t0 in (0, 16):
        J, a = dp_fwd_chunk(J, tids[t0:t0 + 16], cck[t0:t0 + 16],
                            sck[t0:t0 + 16], lv32, kmask, fetch,
                            jnp.int32(27))
        parts.append(np.asarray(a))
    J_ref, args_ref = np_dp_forward(J0, tids, cck, sck, lv32, kmask, fetch,
                                    27)
    assert np.array_equal(np.asarray(Jx), np.asarray(J))
    assert np.array_equal(np.asarray(ax), np.concatenate(parts))
    assert np.array_equal(np.asarray(J), J_ref)
    assert np.array_equal(np.asarray(ax), args_ref)


def test_dp_minplus_numpy_oracle():
    """A 24-slot chunk with every level live: same values AND
    first-occurrence argmin as the numpy replay."""
    chunk, K = 24, 4
    case = _dp_case(11, chunk, K, K, chunk)
    J_ref, args_ref = np_dp_forward(*case)
    J, args = dp_fwd_chunk(*case)
    assert np.array_equal(np.asarray(J), J_ref)
    assert np.array_equal(np.asarray(args), args_ref)


def dp_ties_case():
    """Crafted all-equal-cost fixture: with a zero fetch matrix every
    predecessor ties."""
    K, chunk = 4, 6
    lv32 = jnp.linspace(0.0, 1.0, K, dtype=jnp.float32)
    fetch = dp_fetch_matrix(jnp.float32(0.0), lv32)     # all-zero fetch
    J0 = jnp.asarray([1.0, 0.0, 0.0, 0.0], jnp.float32)
    cck = jnp.zeros((chunk,), jnp.float32)
    sck = jnp.zeros((chunk, K), jnp.float32)            # w == 0
    tids = jnp.arange(chunk, dtype=jnp.int32)
    kmask = jnp.ones((K,), bool)
    return J0, tids, cck, sck, lv32, kmask, fetch, jnp.int32(chunk)


def test_dp_argmin_ties_resolve_to_lowest_index():
    """With every predecessor tied, the argmin table must be the lowest
    index holding the running min."""
    case = dp_ties_case()
    chunk, K = case[3].shape
    J, args = dp_fwd_chunk(*case)
    # slot 0: levels {1,2,3} tie at 0 -> index 1; after that J == 0
    # everywhere so all K levels tie -> index 0
    want = np.ones((chunk, K), np.int64)
    want[1:] = 0
    assert np.array_equal(np.asarray(args), want)
    assert np.array_equal(np.asarray(J), np.zeros(K, np.float32))


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 40), st.integers(2, 6))
def test_dp_argmin_tie_property(seed, chunk, K):
    """Hypothesis: costs drawn from a coarse half-integer grid force
    frequent exact ties; the scan must match the numpy first-occurrence
    (lowest-predecessor-index) oracle bit-for-bit."""
    rng = np.random.default_rng(seed)
    lv32 = jnp.linspace(0.0, 1.0, K, dtype=jnp.float32)
    fetch = dp_fetch_matrix(jnp.float32(rng.integers(0, 3) * 2.0), lv32)
    w = (rng.integers(0, 4, (chunk, K)) / 2.0).astype(np.float32)
    J0 = jnp.asarray(rng.integers(0, 3, K) / 2.0, jnp.float32)
    case = (J0, jnp.arange(chunk, dtype=jnp.int32),
            jnp.zeros(chunk, jnp.float32), jnp.asarray(w), lv32,
            jnp.ones((K,), bool), fetch, jnp.int32(chunk))
    J_ref, args_ref = np_dp_forward(*case)
    J, args = dp_fwd_chunk(*case)
    assert np.array_equal(np.asarray(args), args_ref)
    assert np.array_equal(np.asarray(J), J_ref)


# ---------------------------------------------------------------------
# The counter-keyed uniform (scenarios.base.slot_uniform) against the
# contract every stream relies on: the draws at counters t0 + arange(chunk)
# are the draws at any split of those counters, and each is the draw made
# slot by slot from fold_in(fold_in(key, t), salt).
# ---------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _one_slot_uniform(salt):
    """One slot's draw, from its own folded key alone."""
    def one(key, t):
        k = jax.random.fold_in(key, t)
        if salt is not None:
            k = jax.random.fold_in(k, salt)
        return jax.random.uniform(k, (), jnp.float32)
    return jax.jit(one)


def _check_slot_uniform(key, tids, salt, split):
    got = np.asarray(slot_uniform(key, tids, salt))
    one = _one_slot_uniform(salt)
    want = np.stack([np.asarray(one(key, t)) for t in tids])
    assert np.array_equal(got, want)
    parts = [slot_uniform(key, tids[:split], salt),
             slot_uniform(key, tids[split:], salt)]
    assert np.array_equal(got, np.concatenate([np.asarray(p)
                                               for p in parts]))


@pytest.mark.parametrize("salt", [None, 0, 1, 2, 0x7FFFFFFF])
@pytest.mark.parametrize("chunk", [1, 8, 37, 129])
def test_slot_uniform_bits_match_jax_random(salt, chunk):
    key = jax.random.PRNGKey(42)
    tids = jnp.arange(chunk, dtype=jnp.int32) + 5
    _check_slot_uniform(key, tids, salt, chunk // 3)


def test_bernoulli_bits_match_jax_random():
    """(slot uniform < p) == jax.random.bernoulli on the folded key —
    the exact op chain bernoulli_arrivals / the GE emitter use."""
    key = jax.random.PRNGKey(7)
    tids = jnp.arange(37, dtype=jnp.int32)
    u = slot_uniform(key, tids)
    for p in (0.0, 0.25, 0.4, 1.0):
        want = jax.vmap(lambda t: jax.random.bernoulli(
            jax.random.fold_in(key, t), p))(tids)
        assert np.array_equal(np.asarray(u < p), np.asarray(want)), p


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 70),
       st.one_of(st.none(), st.integers(0, 2 ** 31 - 1)),
       st.integers(0, 10 ** 6))
def test_slot_uniform_property(seed, chunk, salt, t0):
    """Random keys x random salts x non-aligned chunk sizes x arbitrary
    counter offsets x a split point: always the slot-by-slot bits."""
    key = jax.random.PRNGKey(seed)
    tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
    _check_slot_uniform(key, tids, salt, (seed % chunk) if chunk else 0)
