"""Per-kernel interpret-mode validation against the pure-jnp oracles,
with shape/dtype sweeps (repo contract for kernels/)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# minmax_hash
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,h", [(7, 96, 33), (64, 256, 128), (1, 32, 1),
                                   (130, 513, 130)])
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_minmax_hash_matches_ref(rng, n, d, h, density):
    fp = rng.random((n, d)) < density
    mp = rng.integers(0, 2**31 - 1, size=(d, h), dtype=np.int32)
    mins_k, maxs_k = ops.minmax_hash(jnp.asarray(fp), jnp.asarray(mp))
    mins_r, maxs_r = ref.minmax_hash(jnp.asarray(fp), jnp.asarray(mp))
    np.testing.assert_array_equal(np.asarray(mins_k), np.asarray(mins_r))
    np.testing.assert_array_equal(np.asarray(maxs_k), np.asarray(maxs_r))


def test_minmax_hash_empty_rows(rng):
    fp = np.zeros((4, 64), bool)
    mp = rng.integers(0, 2**31 - 1, size=(64, 8), dtype=np.int32)
    mins, maxs = ops.minmax_hash(jnp.asarray(fp), jnp.asarray(mp))
    assert int(jnp.min(mins)) == 2**31 - 1
    assert int(jnp.max(maxs)) == 0


@pytest.mark.parametrize("n,t,n_funcs,use_minmax",
                         [(7, 20, 4, True), (33, 100, 8, True),
                          (16, 12, 4, False), (5, 7, 6, True)])
def test_minmax_sig_buckets_matches_signature_oracle(rng, n, t, n_funcs,
                                                     use_minmax):
    """The fused signature-fold + bucket-addressing kernel epilogue is
    bit-identical to the jnp composition (signatures → bucket_ids) for
    every table layout, including non-multiple table counts."""
    import dataclasses
    from repro.core import lsh as L

    cfg = L.LSHConfig(n_tables=t, n_funcs=n_funcs, use_minmax=use_minmax,
                      seed=99)
    d = 256
    fp = jnp.asarray(rng.random((n, d)) < 0.3)
    mp = L.hash_mappings(d, cfg)
    n_buckets = 1024
    sig_o = L.signatures(fp, mp, cfg)
    bkt_o = L.bucket_ids(sig_o, n_buckets, cfg.seed)
    sig_k, bkt_k = ops.minmax_sig_buckets(
        fp, mp, L.bucket_salts(t, cfg.seed), use_minmax=use_minmax,
        n_buckets=n_buckets)
    np.testing.assert_array_equal(np.asarray(sig_k), np.asarray(sig_o))
    np.testing.assert_array_equal(np.asarray(bkt_k), np.asarray(bkt_o))
    # and through the config-level entry with a validity mask
    pcfg = dataclasses.replace(cfg, use_pallas=True)
    valid = jnp.asarray(rng.random(n) < 0.6)
    s1, b1 = L.signatures_and_buckets(fp, mp, pcfg, n_buckets, valid=valid)
    s2 = L.signatures(fp, mp, cfg, valid=valid)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(
        np.asarray(b1), np.asarray(L.bucket_ids(s2, n_buckets, cfg.seed)))


# ---------------------------------------------------------------------------
# haar2d
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,h,w", [(5, 8, 8), (9, 32, 64), (2, 16, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_haar2d_matches_ref(rng, n, h, w, dtype):
    imgs = rng.standard_normal((n, h, w)).astype(dtype)
    out_k = ops.haar2d(jnp.asarray(imgs))
    out_r = ref.haar2d(jnp.asarray(imgs))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-4, rtol=1e-4)


def test_haar_matrix_orthonormal():
    for n in (2, 8, 64):
        t = ref.haar_matrix(n)
        np.testing.assert_allclose(t @ t.T, np.eye(n), atol=1e-5)


def test_haar2d_preserves_energy(rng):
    imgs = rng.standard_normal((3, 16, 32)).astype(np.float32)
    out = np.asarray(ref.haar2d(jnp.asarray(imgs)))
    np.testing.assert_allclose((out**2).sum(), (imgs**2).sum(), rtol=1e-4)


# ---------------------------------------------------------------------------
# stft_mag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,l,k", [(10, 200, 101), (3, 64, 33), (257, 128,
                                                                 65)])
def test_stft_mag_matches_ref(rng, n, l, k):
    frames = rng.standard_normal((n, l)).astype(np.float32)
    win = np.hanning(l).astype(np.float32)
    dr, di = ref.dft_matrices(l, k)
    args = [jnp.asarray(a) for a in (frames, win, dr, di)]
    out_k = ops.stft_mag(*args)
    out_r = ref.stft_mag(*args)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=5e-4, atol=5e-3)


def test_stft_matches_numpy_rfft(rng):
    x = rng.standard_normal((4, 128)).astype(np.float32)
    win = np.hanning(128).astype(np.float32)
    dr, di = ref.dft_matrices(128, 65)
    ours = np.asarray(ref.stft_mag(*map(jnp.asarray, (x, win, dr, di))))
    theirs = np.abs(np.fft.rfft(x * win, axis=-1)) ** 2
    np.testing.assert_allclose(ours, theirs, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# jaccard_popcount
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p,w", [(16, 4), (513, 8), (1, 256)])
def test_jaccard_matches_ref(rng, p, w):
    a = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(p, w), dtype=np.uint32)
    out_k = ops.jaccard_popcount(jnp.asarray(a), jnp.asarray(b))
    out_r = ref.jaccard_popcount(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-6)


def test_kernels_compile_on_tpu_interpret_on_cpu_else_raise(monkeypatch):
    """The wrappers interpret on the CPU, compile on the TPU, and refuse
    any other platform instead of quietly interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    a = jnp.zeros((4, 2), jnp.uint32)
    with pytest.raises(NotImplementedError, match="gpu"):
        ops.jaccard_popcount(a, a)
    ops.jaccard_popcount(a, a, use_pallas=False)     # the oracle still runs


def test_jaccard_identical_and_disjoint():
    a = np.asarray([[0b1010, 0], [0, 0b1]], np.uint32)
    b = np.asarray([[0b0101, 0], [0, 0b1]], np.uint32)
    out = np.asarray(ref.jaccard_popcount(jnp.asarray(a), jnp.asarray(b)))
    assert out[0] == 0.0 and out[1] == 1.0


def test_verify_epilogue_pallas_parity(rng):
    """The ISSUE-8 exact-Jaccard verify epilogue is bit-identical whether
    it scores through the jnp oracle (``verify=1``) or the Pallas
    popcount kernel in interpret mode (``verify=2``) — both the raw
    ``verify_pairs`` gather and the full ``guarded_step`` emission."""
    import dataclasses
    from repro.core import lsh as L
    from repro.stream import index as SI

    lcfg = L.LSHConfig(n_tables=20, n_funcs=4, n_matches=2, bucket_cap=4,
                       min_dt=0)
    icfg = SI.StreamIndexConfig(n_buckets=256, bucket_cap=4, pk_slots=64,
                                pk_words=4)
    n = 32
    packed = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
    packed = packed.at[20].set(packed[5])     # one exact repeat
    bits = np.unpackbits(np.asarray(packed).view(np.uint8), axis=1,
                         bitorder="little")
    sigs = L.signatures(jnp.asarray(bits), L.hash_mappings(128, lcfg), lcfg)
    ids = jnp.arange(n, dtype=jnp.int32)
    buckets = L.bucket_ids(sigs, icfg.n_buckets, lcfg.seed)

    # raw verify_pairs: oracle vs Pallas on the same ring + candidates
    state = dataclasses.replace(SI.init_index(lcfg, icfg),
                                pk=jnp.zeros((64, 4), jnp.uint32)
                                .at[ids % 64].set(packed))
    cand = L.Pairs(idx1=ids[:16], idx2=jnp.roll(ids, 7)[:16],
                   sim=jnp.ones(16, jnp.float32),
                   valid=jnp.asarray(rng.random(16) < 0.75))
    j_ref = np.asarray(SI.verify_pairs(state, cand, use_pallas=False))
    j_pal = np.asarray(SI.verify_pairs(state, cand, use_pallas=True))
    np.testing.assert_array_equal(j_ref, j_pal)

    # full in-dispatch epilogue: identical VerifiedPairs either route
    def step(verify):
        _, pairs, _ = SI.guarded_step(
            SI.init_index(lcfg, icfg), sigs, buckets, ids, None, lcfg,
            window=0, packed=packed, max_pairs=32, verify=verify)
        return pairs
    p1, p2 = step(1), step(2)
    for f in ("idx1", "idx2", "sim", "jac", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(p1, f)),
                                      np.asarray(getattr(p2, f)))
    assert np.asarray(p1.valid).any()   # the parity claim is non-vacuous


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d", [
    (2, 4, 2, 128, 128, 64),
    (1, 8, 1, 64, 64, 32),
    (2, 4, 4, 8, 128, 64),     # decode-ish: short q against long cache
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(rng, b, hq, hkv, sq, sk, d, causal):
    q = rng.standard_normal((b, hq, sq, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, sk, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, sk, d)).astype(np.float32)
    out_k = ops.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), causal=causal,
                                bq=min(64, sq), bk=64)
    out_r = ref.flash_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-5)


# ---------------------------------------------------------------------------
# fused mamba scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,di,n,bd", [(2, 16, 8, 4, 8), (1, 33, 24, 5, 8),
                                         (3, 8, 128, 16, 128)])
def test_mamba_scan_matches_ref(rng, b, s, di, n, bd):
    xdt = rng.standard_normal((b, s, di)).astype(np.float32)
    dt = np.abs(rng.standard_normal((b, s, di))).astype(np.float32) * 0.1
    a = -np.abs(rng.standard_normal((di, n))).astype(np.float32)
    bm = rng.standard_normal((b, s, n)).astype(np.float32)
    cm = rng.standard_normal((b, s, n)).astype(np.float32)
    args = [jnp.asarray(x) for x in (xdt, dt, a, bm, cm)]
    yk, hk = ops.mamba_scan(*args, bd=bd)
    yr, hr = ref.mamba_scan(*args)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), atol=2e-5)


def test_mamba_scan_consistent_with_model_scan(rng):
    """Kernel semantics == the model's chunked associative scan."""
    from repro.models.ssm import mamba1_scan
    b, s, di, n = 2, 32, 8, 4
    xdt = rng.standard_normal((b, s, di)).astype(np.float32)
    dt = np.abs(rng.standard_normal((b, s, di))).astype(np.float32) * 0.1
    a = -np.abs(rng.standard_normal((di, n))).astype(np.float32)
    bm = rng.standard_normal((b, s, n)).astype(np.float32)
    cm = rng.standard_normal((b, s, n)).astype(np.float32)
    yk, hk = ref.mamba_scan(*[jnp.asarray(x) for x in (xdt, dt, a, bm, cm)])
    da = dt[..., None] * a[None, None]
    y2, h2 = mamba1_scan(jnp.asarray(xdt), jnp.asarray(da), jnp.asarray(bm),
                         jnp.asarray(cm), jnp.zeros((b, di, n)), chunk=8)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(h2), atol=1e-4)
