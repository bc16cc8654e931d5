"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret mode
(assignment requirement: every kernel sweeps shapes/dtypes against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.gemm_os import gemm_os, pick_blocks
from repro.kernels.offload_pack import fp8_pack, fp8_unpack
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-1)])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 512, 384, 128, 128, 128),
    (256, 1024, 256, 128, 128, 256),
    (512, 256, 512, 256, 256, 128),
])
def test_gemm_os_sweep(m, k, n, bm, bn, bk, dtype, tol):
    x = jax.random.normal(KEY, (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(dtype)
    y = gemm_os(x, w, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_gemm_pick_blocks_aligned():
    for m, k, n in [(256, 8192, 22528), (4096, 512, 1024), (128, 128, 128)]:
        bm, bn, bk = pick_blocks(m, k, n)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0
        assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("B,H,Hkv,S,T,d,causal,window", [
    (2, 4, 2, 128, 128, 64, True, 0),
    (1, 4, 4, 256, 256, 32, True, 64),
    (2, 8, 2, 96, 160, 64, False, 0),
    (1, 2, 1, 64, 192, 128, True, 0),
])
def test_flash_attention_sweep(B, H, Hkv, S, T, d, causal, window,
                               dtype, tol):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, d)).astype(dtype)
    o = flash_attention_fwd(q, k, v, causal=causal, window=window,
                            bq=64, bk=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


# largest gradient error over the largest reference gradient: f32 reads
# about 1e-6 (summation order); bf16 about 7e-3, the rounding of p and dS
# as MXU operands and of the bf16 gradients (2**-8 = 3.9e-3) themselves
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,K,G,S,hd,causal,window", [
    (2, 3, 3, 640, 64, True, 0),        # smollm groups and head dim
    (1, 2, 4, 1100, 80, True, 0),       # danube's; two padded tiles
    (1, 2, 4, 1024, 80, True, 200),     # sliding window
    (2, 2, 1, 200, 128, False, 0),      # MHA, non-causal, padded keys
], ids=["gqa3_hd64_causal", "gqa4_hd80_causal", "gqa4_hd80_window",
        "mha_hd128_full"])
def test_flash_attention_grads_match_ref(B, K, G, S, hd, causal, window,
                                         dtype, tol):
    """dQ, dK, dV of the kernel pair against the dense reference under
    ``jax.grad``: through ``ops.flash_attention`` (model layout, tiles from
    the shapes), and through the kernels at 128-row tiles, where blocks
    above the diagonal or below the window are skipped."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, K * G, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd)).astype(dtype)
    w = jax.random.normal(ks[3], (B, S, K * G, hd))

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal, window)
        return jnp.sum(o.astype(jnp.float32) * w)

    def loss_ref(q, k, v):
        o = ref.attention_ref(*(x.astype(jnp.float32).swapaxes(1, 2)
                                for x in (q, k, v)),
                              causal=causal, window=window)
        return jnp.sum(o.swapaxes(1, 2) * w)

    heads = [x.swapaxes(1, 2) for x in (q, k, v)]
    kw = dict(causal=causal, window=window, bq=128, bk=128, interpret=True)
    o, lse = flash_attention_fwd(*heads, save_lse=True, **kw)
    small = flash_attention_bwd(*heads, o, lse,
                                w.swapaxes(1, 2).astype(dtype), **kw)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g in (jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
              [x.swapaxes(1, 2) for x in small]):
        for name, a, b in zip("qkv", g, gr):
            assert a.dtype == dtype and a.shape == b.shape
            b = np.asarray(b, np.float32)
            err = np.max(np.abs(np.asarray(a, np.float32) - b)) \
                / np.abs(b).max()
            assert err < tol, (f"d{name}", err)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("BH,S,P,N,c", [
    (3, 64, 16, 8, 16),
    (2, 128, 32, 16, 32),
    (1, 256, 64, 64, 128),
])
def test_ssd_scan_sweep(BH, S, P, N, c, dtype, tol):
    ks = jax.random.split(KEY, 4)
    x = (jax.random.normal(ks[0], (BH, S, P)) * 0.5).astype(dtype)
    a = -jax.nn.softplus(jax.random.normal(ks[1], (BH, S)))
    B = (jax.random.normal(ks[2], (BH, S, N)) * 0.4).astype(dtype)
    C = (jax.random.normal(ks[3], (BH, S, N)) * 0.4).astype(dtype)
    y = ssd_scan(x, a, B, C, chunk=c, interpret=True)
    for i in range(BH):
        want, _ = ref.ssd_ref(x[i], a[i], B[i], C[i])
        np.testing.assert_allclose(np.asarray(y[i], np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol * 5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,C,br", [(256, 64, 64), (128, 128, 128),
                                    (512, 32, 64)])
def test_fp8_pack_sweep(R, C, br):
    x = jax.random.normal(KEY, (R, C)) * 5.0
    q, s = fp8_pack(x, block_rows=br, interpret=True)
    qr, sr = ref.fp8_pack_ref(x, br)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(q, np.float32),
                               np.asarray(qr, np.float32))
    y = fp8_unpack(q, s, block_rows=br, dtype=jnp.float32, interpret=True)
    yr = ref.fp8_unpack_ref(qr, sr, br, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.04       # blockwise scales beat the per-tensor bound


@pytest.mark.parametrize("R,C,br", [(256, 64, 64), (128, 128, 128),
                                    (512, 32, 64)])
def test_int8_pack_sweep(R, C, br):
    from repro.kernels.offload_pack import int8_pack, int8_unpack
    x = jax.random.normal(KEY, (R, C)) * 5.0
    q, s = int8_pack(x, block_rows=br, interpret=True)
    qr, sr = ref.int8_pack_ref(x, br)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(q, np.int32),
                                  np.asarray(qr, np.int32))
    y = int8_unpack(q, s, block_rows=br, dtype=jnp.float32, interpret=True)
    yr = ref.int8_unpack_ref(qr, sr, br, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.02       # int8 round-to-nearest, blockwise scale


# ---------------------------------------------------------------------------
# registry-parametrized codec round trips: every codec registered in
# core/compress.py is swept automatically — a future register_codec entry
# is covered the moment it lands, kernel twin and all, without naming it
# here.  Asserts Pallas kernel twin == pure-jnp ref twin on the SAME blocks.
from repro.core.compress import (decode_tensor, encode_tensor,  # noqa: E402
                                 get_codec, registered_codecs)


@pytest.mark.parametrize("name", registered_codecs())
@pytest.mark.parametrize("R,C,br", [(256, 64, 64), (128, 128, 128)])
def test_codec_registry_kernel_vs_ref_blocks(name, R, C, br):
    codec = get_codec(name)
    if not codec.has_kernel:
        pytest.skip(f"codec {name!r} registered without a kernel twin")
    x = jax.random.normal(KEY, (R, C)) * 5.0
    q, s = codec.pack(x, block_rows=br, interpret=True)
    qr, sr = codec.pack_ref(x, br)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    y = codec.unpack(q, s, block_rows=br, dtype=jnp.float32, interpret=True)
    yr = codec.unpack_ref(qr, sr, br, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5)
    # the quantize-dequantize error stays inside the codec's blockwise bound
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.05, (name, rel)


def test_blocksparse_codec_prunes_small_entries():
    """The block-sparse codec's defining property: entries below
    absmax/32 land as EXACT zeros (zero-run-rich payload for a wire-side
    entropy stage), large entries survive int8 quantization, and the
    round trip stays inside the registry error bound."""
    from repro.core import compress as comp
    from repro.kernels.offload_pack import BLOCKSPARSE_TAU
    # the jnp compress path (core, pallas-free imports) and the Pallas
    # kernel twin must prune at the same threshold
    assert comp.BLOCKSPARSE_TAU == BLOCKSPARSE_TAU
    codec = get_codec("blocksparse")
    x = jax.random.normal(KEY, (256, 64)) * 2.0
    q, s = codec.pack(x, block_rows=64, interpret=True)
    xb = np.asarray(x, np.float32).reshape(4, 64, 64)
    absmax = np.abs(xb).max(axis=(1, 2))
    small = np.abs(xb) < (absmax / BLOCKSPARSE_TAU)[:, None, None]
    qb = np.asarray(q, np.int32).reshape(4, 64, 64)
    assert (qb[small] == 0).all()           # pruned to exact zero
    assert (qb[~small] != 0).all()          # kept entries quantize nonzero
    # measurably sparser than the plain int8 twin on the same data
    q_int8, _ = get_codec("int8").pack(x, block_rows=64, interpret=True)
    frac = float((qb == 0).mean())
    frac_int8 = float((np.asarray(q_int8, np.int32) == 0).mean())
    assert frac > frac_int8 and frac >= 0.03
    y = codec.unpack(q, s, block_rows=64, dtype=jnp.float32, interpret=True)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.05


@pytest.mark.parametrize("name", registered_codecs())
def test_codec_registry_tensor_twins(name):
    """encode/decode_tensor (the paged spill path) agree between the
    per-tensor ref path and the single-block kernel path, for any rank."""
    codec = get_codec(name)
    x = jax.random.normal(KEY, (3, 8, 4, 16)) * 3.0
    q, s = encode_tensor(codec, x)
    y = decode_tensor(codec, q, s, jnp.float32)
    assert q.shape == x.shape and y.shape == x.shape
    if codec.has_kernel:
        qk, sk = encode_tensor(codec, x, kernel=True)
        np.testing.assert_array_equal(np.asarray(q, np.float32),
                                      np.asarray(qk, np.float32))
        np.testing.assert_allclose(float(s), float(sk), rtol=1e-6)
        yk = decode_tensor(codec, qk, sk, jnp.float32, kernel=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yk), rtol=1e-6)
    # lossy but bounded
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.08, (name, rel)


# ---------------------------------------------------------------------------
# paged decode attention: in-kernel block-table lookup vs the gather-then-
# decode_attention twin (the tentpole's bit-identity contract)
def _paged_setup(B, H, K, hd, page, pp, seed=0):
    rng = np.random.default_rng(seed)
    P = B * pp + 1                               # frames incl. scratch
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, page, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, page, K, hd)), jnp.float32)
    # a permuted map with unowned tail entries routed to scratch — the
    # layout the PagedKVCacheManager actually produces
    pm = rng.permutation(P - 1)[:B * pp].reshape(B, pp).astype(np.int32)
    pm[0, -1] = P - 1                            # one scratch-routed entry
    return q, kp, vp, jnp.asarray(pm)


@pytest.mark.parametrize("page,pp", [(4, 6), (8, 4), (16, 2)])
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("H,K", [(4, 4), (4, 2), (6, 2)])
def test_paged_decode_parity_sweep(page, pp, window, softcap, H, K):
    """Kernel == XLA ref twin across page size x window x softcap x GQA,
    at several cache fills including page boundaries."""
    from repro.kernels.paged_attention import paged_decode_attention
    q, kp, vp, pm = _paged_setup(2, H, K, 32, page, pp)
    for idx in (0, page - 1, page, pp * page - 1):
        got = paged_decode_attention(q, kp, vp, pm, jnp.int32(idx),
                                     window=window, softcap=softcap,
                                     interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, pm, jnp.int32(idx),
                                              window=window, softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("name", registered_codecs())
def test_paged_decode_fused_codec_parity(name):
    """Compressed side-pool pages dequant inside the K/V load exactly as
    decode_tensor would inflate them — for every registered codec."""
    from repro.kernels.paged_attention import paged_decode_attention
    codec = get_codec(name)
    B, H, K, hd, page, pp = 2, 4, 2, 32, 8, 3
    q, kp, vp, pm = _paged_setup(B, H, K, hd, page, pp, seed=1)
    P = kp.shape[0]
    pmn = np.asarray(pm).copy()
    C = 3
    kq = [None] * C
    vq = [None] * C
    ks = np.zeros((C, 1), np.float32)
    vs = np.zeros((C, 1), np.float32)
    for ci, fr in enumerate({int(pmn[0, 0]), int(pmn[1, 1]),
                             int(pmn[0, 1])}):
        qk, sk = encode_tensor(codec, kp[fr])
        qv, sv = encode_tensor(codec, vp[fr])
        kq[ci], ks[ci, 0] = np.asarray(qk), float(sk)
        vq[ci], vs[ci, 0] = np.asarray(qv), float(sv)
        pmn[pmn == fr] = P + ci                  # translate to side ids
    kq, vq = jnp.asarray(np.stack(kq)), jnp.asarray(np.stack(vq))
    ks, vs = jnp.asarray(ks), jnp.asarray(vs)
    pmc = jnp.asarray(pmn)
    idx = jnp.int32(pp * page - 1)
    got = paged_decode_attention(q, kp, vp, pmc, idx, kq_pool=kq,
                                 vq_pool=vq, k_scale=ks, v_scale=vs,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pmc, idx, kq_pool=kq,
                                          vq_pool=vq, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
    # the fused path genuinely used the side pool: the raw frames it
    # replaced disagree with the compressed decode
    raw = ref.paged_decode_attention_ref(q, kp, vp, pm, idx)
    assert not np.allclose(np.asarray(got), np.asarray(raw))


def test_paged_decode_inactive_slot_finite():
    """cache_index=-1 masks every row: the output must be finite garbage
    (discarded by the engine mask), never NaN — the decode-path NaN bug."""
    from repro.kernels.paged_attention import paged_decode_attention
    q, kp, vp, pm = _paged_setup(2, 4, 2, 32, 8, 3)
    got = paged_decode_attention(q, kp, vp, pm, jnp.int32(-1),
                                 interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    want = ref.paged_decode_attention_ref(q, kp, vp, pm, jnp.int32(-1))
    assert np.isfinite(np.asarray(want)).all()


def test_paged_attention_impl_registry():
    """ops.paged_attention dispatches by registry flag; unknown impls are
    rejected; both impls agree on the same inputs."""
    q, kp, vp, pm = _paged_setup(1, 2, 2, 16, 4, 2)
    a = ops.paged_attention(q, kp, vp, pm, jnp.int32(5), impl="pallas")
    b = ops.paged_attention(q, kp, vp, pm, jnp.int32(5), impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-6, atol=2e-6)
    with pytest.raises(ValueError):
        ops.set_paged_impl("cuda")
    assert ops._PAGED_IMPL["default"] == "pallas"
    ops.set_paged_impl("xla")
    try:
        c = ops.paged_attention(q, kp, vp, pm, jnp.int32(5))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))
    finally:
        ops.set_paged_impl("pallas")
