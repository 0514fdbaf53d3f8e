"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU) + hypothesis property tests."""
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
# Hosting kernels (kernels.hosting): DP min-plus + counter-keyed threefry.
# Unlike the float kernels above these are bit-EXACT vs the engine's XLA
# references — array_equal, never allclose (the backend-dispatch
# invariant: a backend is a performance knob, not a numerics choice).
# ----------------------------------------------------------------------

from repro.core.policies.offline_opt import (dp_fetch_matrix, dp_frontier0,
                                             dp_fwd_chunk)
from repro.kernels.hosting import slot_uniform_tc, threefry_fold


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


@pytest.mark.parametrize("chunk,K,k_used,T_len", DP_CASES)
def test_dp_minplus_matches_xla_reference(chunk, K, k_used, T_len):
    case = _dp_case(chunk * 7 + K, chunk, K, k_used, T_len)
    Jx, ax = dp_fwd_chunk(*case, "xla")
    Jp, ap = dp_fwd_chunk(*case, "pallas")
    assert np.array_equal(np.asarray(Jx), np.asarray(Jp))
    assert np.array_equal(np.asarray(ax), np.asarray(ap))


def test_dp_minplus_chained_chunks_match():
    """The frontier carried across chunk boundaries stays exact: two
    16-slot pallas chunks == one 32-slot xla chunk, J and args."""
    (J0, _, cck, sck, lv32, kmask, fetch, _) = _dp_case(3, 32, 5, 4, 32)
    Jx, ax = dp_fwd_chunk(J0, jnp.arange(32, dtype=jnp.int32), cck, sck,
                          lv32, kmask, fetch, jnp.int32(27), "xla")
    J, parts = J0, []
    for t0 in (0, 16):
        tids = t0 + jnp.arange(16, dtype=jnp.int32)
        J, a = dp_fwd_chunk(J, tids, cck[t0:t0 + 16], sck[t0:t0 + 16],
                            lv32, kmask, fetch, jnp.int32(27), "pallas")
        parts.append(np.asarray(a))
    assert np.array_equal(np.asarray(Jx), np.asarray(J))
    assert np.array_equal(np.asarray(ax), np.concatenate(parts))


def test_dp_minplus_numpy_oracle():
    """Independent float32 numpy replay of the recursion — same values AND
    first-occurrence argmin (np.argmin's documented tie rule)."""
    chunk, K = 24, 4
    case = _dp_case(11, chunk, K, K, chunk)
    J0, tids, cck, sck, lv32, kmask, fetch, T_len = case
    w = np.asarray(cck)[:, None] * np.asarray(lv32)[None, :] + np.asarray(sck)
    J, args_ref = np.asarray(J0), []
    fm = np.asarray(fetch)
    for t in range(chunk):
        trans = (J[:, None] + fm).astype(np.float32)
        args_ref.append(trans.argmin(axis=0))
        J = (trans.min(axis=0) + w[t]).astype(np.float32)
    for backend in ("xla", "pallas"):
        Jb, ab = dp_fwd_chunk(*case, backend)
        assert np.array_equal(np.asarray(Jb), J), backend
        assert np.array_equal(np.asarray(ab), np.stack(args_ref)), backend


def test_dp_argmin_ties_resolve_to_lowest_index():
    """Crafted all-equal-cost fixture: with a zero fetch matrix every
    predecessor ties, so the argmin table must be the lowest index holding
    the running min — for both backends, identically."""
    K, chunk = 4, 6
    lv32 = jnp.linspace(0.0, 1.0, K, dtype=jnp.float32)
    fetch = dp_fetch_matrix(jnp.float32(0.0), lv32)     # all-zero fetch
    J0 = jnp.asarray([1.0, 0.0, 0.0, 0.0], jnp.float32)
    cck = jnp.zeros((chunk,), jnp.float32)
    sck = jnp.zeros((chunk, K), jnp.float32)            # w == 0
    tids = jnp.arange(chunk, dtype=jnp.int32)
    kmask = jnp.ones((K,), bool)
    for backend in ("xla", "pallas"):
        J, args = dp_fwd_chunk(J0, tids, cck, sck, lv32, kmask, fetch,
                               jnp.int32(chunk), backend)
        # slot 0: levels {1,2,3} tie at 0 -> index 1; after that J == 0
        # everywhere so all K levels tie -> index 0
        want = np.ones((chunk, K), np.int64)
        want[1:] = 0
        assert np.array_equal(np.asarray(args), want), backend
        assert np.array_equal(np.asarray(J), np.zeros(K, np.float32)), backend


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 40), st.integers(2, 6))
def test_dp_argmin_tie_property(seed, chunk, K):
    """Hypothesis: costs drawn from a coarse half-integer grid force
    frequent exact ties; both backends must match the numpy
    first-occurrence (lowest-predecessor-index) oracle bit-for-bit."""
    rng = np.random.default_rng(seed)
    lv32 = jnp.linspace(0.0, 1.0, K, dtype=jnp.float32)
    fetch = dp_fetch_matrix(jnp.float32(rng.integers(0, 3) * 2.0), lv32)
    w = (rng.integers(0, 4, (chunk, K)) / 2.0).astype(np.float32)
    J0 = jnp.asarray(rng.integers(0, 3, K) / 2.0, jnp.float32)
    tids = jnp.arange(chunk, dtype=jnp.int32)
    kmask = jnp.ones((K,), bool)
    J, args_ref = np.asarray(J0), []
    fm = np.asarray(fetch)
    for t in range(chunk):
        trans = (J[:, None] + fm).astype(np.float32)
        args_ref.append(trans.argmin(axis=0))
        J = (trans.min(axis=0) + w[t]).astype(np.float32)
    for backend in ("xla", "pallas"):
        Jb, ab = dp_fwd_chunk(J0, tids, jnp.zeros(chunk, jnp.float32),
                              jnp.asarray(w), lv32, kmask, fetch,
                              jnp.int32(chunk), backend)
        assert np.array_equal(np.asarray(ab), np.stack(args_ref)), backend
        assert np.array_equal(np.asarray(Jb), J), backend


def test_dp_minplus_batched_wrapper():
    """ops.dp_minplus vmaps the kernel over [B]; rows match per-instance
    XLA references exactly."""
    cases = [_dp_case(s, 20, 5, k, t)
             for s, (k, t) in enumerate([(5, 20), (3, 7), (2, 0)])]
    J = jnp.stack([c[0] for c in cases])
    w = []
    n_live = []
    for c in cases:
        _, tids, cck, sck, lv32, kmask, _, T_len = c
        wck = cck[:, None] * lv32[None, :] + sck
        w.append(jnp.where(kmask[None, :], wck, jnp.inf))
        n_live.append(T_len - tids[0])
    Jb, ab = ops.dp_minplus(J, jnp.stack(w),
                            jnp.stack([c[6] for c in cases]),
                            jnp.stack(n_live))
    for i, c in enumerate(cases):
        Jx, ax = dp_fwd_chunk(*c, "xla")
        assert np.array_equal(np.asarray(Jb[i]), np.asarray(Jx)), i
        assert np.array_equal(np.asarray(ab[i]), np.asarray(ax)), i


# ---------------------------------------------------------------------
# Counter-PRNG kernel vs jax.random primitives (bit-equality).
# ---------------------------------------------------------------------

def _ref_uniform(key, tids, salt):
    """The canonical vmapped chain from scenarios.base.slot_uniform."""
    ks = jax.vmap(lambda t: jax.random.fold_in(key, t))(tids)
    if salt is not None:
        ks = jax.vmap(lambda k: jax.random.fold_in(k, salt))(ks)
    return jax.vmap(lambda k: jax.random.uniform(k, (), jnp.float32))(ks)


@pytest.mark.parametrize("salt", [None, 0, 1, 2, 0x7FFFFFFF])
@pytest.mark.parametrize("chunk", [1, 8, 37, 129])
def test_slot_uniform_bits_match_jax_random(salt, chunk):
    key = jax.random.PRNGKey(42)
    tids = jnp.arange(chunk, dtype=jnp.int32) + 5
    got = slot_uniform_tc(jnp.asarray(key, jnp.uint32), tids, salt)
    assert np.array_equal(np.asarray(got), np.asarray(_ref_uniform(key, tids, salt)))


def test_threefry_fold_matches_fold_in():
    """The in-kernel threefry2x32 reimplementation == jax.random.fold_in
    at the key level, not just after the uniform mapping."""
    key = jax.random.PRNGKey(3)
    d = jnp.arange(64, dtype=jnp.uint32) * 977 + 13
    x0, x1 = threefry_fold(jnp.uint32(key[0]), jnp.uint32(key[1]), d)
    want = jax.vmap(lambda t: jax.random.fold_in(key, t))(d)
    assert np.array_equal(np.asarray(jnp.stack([x0, x1], -1)),
                          np.asarray(want))


def test_bernoulli_bits_match_jax_random():
    """(kernel uniform < p) == jax.random.bernoulli on the folded key —
    the exact op chain bernoulli_arrivals / the GE emitter use."""
    key = jax.random.PRNGKey(7)
    tids = jnp.arange(37, dtype=jnp.int32)
    for p in (0.0, 0.25, 0.4, 1.0):
        u = slot_uniform_tc(jnp.asarray(key, jnp.uint32), tids, None)
        want = jax.vmap(lambda t: jax.random.bernoulli(
            jax.random.fold_in(key, t), p))(tids)
        assert np.array_equal(np.asarray(u < p), np.asarray(want)), p


def test_counter_uniforms_batched_wrapper():
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    tids = jnp.arange(23, dtype=jnp.int32) + 100
    got = ops.counter_uniforms(jnp.asarray(keys, jnp.uint32), tids, salt=2)
    for i in range(4):
        want = _ref_uniform(keys[i], tids, 2)
        assert np.array_equal(np.asarray(got[i]), np.asarray(want)), i


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 70),
       st.one_of(st.none(), st.integers(0, 2 ** 31 - 1)),
       st.integers(0, 10 ** 6))
def test_slot_uniform_property(seed, chunk, salt, t0):
    """Random keys x random salts x non-aligned chunk sizes x arbitrary
    counter offsets: always the exact jax.random bits."""
    key = jax.random.PRNGKey(seed)
    tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
    got = slot_uniform_tc(jnp.asarray(key, jnp.uint32), tids, salt)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_ref_uniform(key, tids, salt)))


@pytest.mark.skipif(jax.default_backend() == "cpu",
                    reason="compiled (non-interpret) Pallas needs an "
                           "accelerator backend; CPU covers interpret mode")
def test_hosting_kernels_compiled_mode():
    """On an accelerator the compiled kernels must match too (interpret
    mode is what the CPU suite proves)."""
    case = _dp_case(1, 32, 5, 4, 25)
    Jx, ax = dp_fwd_chunk(*case, "xla")
    from repro.kernels.hosting import dp_minplus_kc
    J0, tids, cck, sck, lv32, kmask, fetch, T_len = case
    wck = jnp.where(kmask[None, :],
                    cck[:, None] * lv32[None, :] + sck, jnp.inf)
    Jp, ap = dp_minplus_kc(J0, wck, fetch, T_len - tids[0], interpret=False)
    assert np.array_equal(np.asarray(Jx), np.asarray(Jp))
    assert np.array_equal(np.asarray(ax), np.asarray(ap))
    key = jax.random.PRNGKey(9)
    u = slot_uniform_tc(jnp.asarray(key, jnp.uint32),
                        jnp.arange(37, dtype=jnp.int32), 1, interpret=False)
    want = _ref_uniform(key, jnp.arange(37, dtype=jnp.int32), 1)
    assert np.array_equal(np.asarray(u), np.asarray(want))
