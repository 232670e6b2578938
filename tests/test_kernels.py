"""Pallas kernel validation (interpret mode on the CPU backend):
shape/dtype sweeps, assert_allclose vs the pure-jnp oracles in
kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (flash_attention, fused_sample,
                               ragged_decode_attention)
from repro.kernels.ragged_decode_attention import (PAGES_PER_BLOCK,
                                                   paged_decode_attention)
from repro.kernels.ref import (KV_INT8_DECODE_ATOL, flash_attention_ref,
                               fused_sample_ref, gather_pages,
                               paged_decode_attention_ref,
                               quantize_pages_ref, ragged_decode_attention_ref)

pytestmark = pytest.mark.slow   # jit-heavy: Pallas interpret-mode sweeps

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,H,Kh,D,S,bk", [
    (4, 8, 2, 64, 256, 128),
    (2, 16, 16, 128, 512, 128),
    (3, 4, 1, 128, 384, 128),
    (1, 8, 4, 256, 256, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_decode_attention(B, H, Kh, D, S, bk, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kh, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kh, D), dtype)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = ragged_decode_attention(q, k, v, kv_len, block_k=bk)
    ref = ragged_decode_attention_ref(q, k, v, kv_len)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_ragged_decode_attention_softcap():
    ks = jax.random.split(KEY, 4)
    B, H, Kh, D, S = 2, 4, 2, 64, 256
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, Kh, D))
    v = jax.random.normal(ks[2], (B, S, Kh, D))
    kv_len = jnp.array([100, 256])
    out = ragged_decode_attention(q, k, v, kv_len, softcap=20.0)
    ref = ragged_decode_attention_ref(q, k, v, kv_len, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_ragged_decode_length_one():
    """kv_len=1 edge: only the first cache row is attended."""
    ks = jax.random.split(KEY, 3)
    B, H, Kh, D, S = 2, 4, 4, 64, 128
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, Kh, D))
    v = jax.random.normal(ks[2], (B, S, Kh, D))
    kv_len = jnp.ones((B,), jnp.int32)
    out = ragged_decode_attention(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(v[:, 0]), atol=1e-5)


def _paged_inputs(key, B, H, Kh, D, P, N, nb, dtype=jnp.float32, L=2,
                  lens=()):
    """A layered pool, block tables padded with the garbage page 0 past
    each slot's pages, kv_len with the first entries set to ``lens``, and
    the new token's k/v rows.  The rest of kv_len is drawn so that the
    kv_len + 1 rows attended, the new token's among them, are drawn in
    [1, nb*P] as the attended lengths of the dense-view tests were."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (L, N, P, Kh, D), dtype)
    vp = jax.random.normal(ks[2], (L, N, P, Kh, D), dtype)
    bt = np.array(jax.random.randint(ks[3], (B, nb), 1, N), np.int32)
    kv_len = np.array(jax.random.randint(ks[4], (B,), 1, nb * P + 1)) - 1
    kv_len[:len(lens)] = lens
    for b, n in enumerate(kv_len):
        bt[b, n // P + 1:] = 0
    kn, vn = (jax.random.normal(k, (B, Kh, D), dtype)
              for k in jax.random.split(jax.random.fold_in(key, 1)))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), kn, vn


PAGED_CASES = [(4, 8, 2, 64, 128, 9, 2), (2, 16, 16, 128, 128, 17, 3),
               (1, 4, 1, 256, 256, 5, 2)]
# at the engine's 16-token pages, with the first slots at the edges
# (``_edge_lens``); the kernel takes PAGES_PER_BLOCK pages a block
PAGED_EDGE_CASES = [
    (5, 16, 8, 128, 16, 40, PAGES_PER_BLOCK),           # one whole block
    (5, 8, 2, 64, 16, 90, 2 * PAGES_PER_BLOCK + 8),     # three, last padded
    (4, 14, 2, 64, 16, 30, PAGES_PER_BLOCK + 4),        # two padded; 7:1 GQA
    (3, 8, 4, 128, 16, 30, 1),                          # one page, one block
]


def _edge_lens(case):
    """An inactive slot (kv_len 0), the new token on a page's last row
    (P-1) and first row (P), and a full table (nb*P-1)."""
    if case not in PAGED_EDGE_CASES:
        return ()
    B, P, nb = case[0], case[4], case[6]
    return [0, P - 1, min(P, nb * P - 1), nb * P - 1][:B]


@pytest.mark.parametrize("B,H,Kh,D,P,N,nb", PAGED_CASES + PAGED_EDGE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, H, Kh, D, P, N, nb, dtype):
    """Kernel on the layered pool == its jnp reference (gather the
    layer's pages, write the new row, attend), in every layer, with
    several pages a block, blocks past kv_len skipped and the table
    padded with the garbage page."""
    case = (B, H, Kh, D, P, N, nb)
    q, kp, vp, bt, kv_len, kn, vn = _paged_inputs(
        KEY, *case, dtype, lens=_edge_lens(case))
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    for layer in range(kp.shape[0]):
        out = paged_decode_attention(q, kp, vp, bt, kv_len, layer, kn, vn)
        ref = paged_decode_attention_ref(q, kp, vp, bt, kv_len, layer, kn,
                                         vn)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol,
                                   rtol=tol)


def test_paged_matches_dense_on_shared_pages():
    """Two slots mapping the SAME physical prefix pages (GRPO sharing)
    attend exactly as two dense slots holding copies of that prefix."""
    ks = jax.random.split(KEY, 6)
    H, Kh, D, P, N, L = 4, 2, 64, 128, 6, 2
    q = jax.random.normal(ks[0], (2, H, D))
    kp = jax.random.normal(ks[1], (L, N, P, Kh, D))
    vp = jax.random.normal(ks[2], (L, N, P, Kh, D))
    kn = jax.random.normal(ks[3], (2, Kh, D))
    vn = jax.random.normal(ks[4], (2, Kh, D))
    # slot 0: pages [1, 2]; slot 1 shares prefix page 1, then diverges to 3
    bt = jnp.array([[1, 2], [1, 3]], jnp.int32)
    kv_len = jnp.array([200, 170], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, kv_len, 1, kn, vn)
    rows = jnp.arange(2), kv_len
    dense_k = gather_pages(kp[1], bt).at[rows].set(kn)
    dense_v = gather_pages(vp[1], bt).at[rows].set(vn)
    ref = ragged_decode_attention_ref(q, dense_k, dense_v, kv_len + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6,
                               rtol=2e-6)


def test_paged_decode_attention_softcap():
    """A partial page and two whole pages, the new token on a third."""
    q, kp, vp, bt, kv_len, kn, vn = _paged_inputs(KEY, 2, 4, 2, 64, 128, 7,
                                                  3, lens=[100, 256])
    out = paged_decode_attention(q, kp, vp, bt, kv_len, 1, kn, vn,
                                 softcap=20.0)
    ref = paged_decode_attention_ref(q, kp, vp, bt, kv_len, 1, kn, vn,
                                     softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("B,S,H,Kh,D,w", [
    (2, 256, 4, 2, 64, 0),
    (1, 512, 8, 8, 128, 0),
    (2, 256, 4, 2, 64, 128),   # sliding window (gemma2 local layers)
    (1, 384, 6, 2, 64, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, Kh, D, w, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kh, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kh, D), dtype)
    bq = 128 if S % 128 == 0 else 64
    out = flash_attention(q, k, v, block_q=bq, block_k=bq, window=w)
    ref = flash_attention_ref(q, k, v, causal=True, window=w)
    tol = 3e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_softcap():
    ks = jax.random.split(KEY, 3)
    B, S, H, Kh, D = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Kh, D))
    v = jax.random.normal(ks[2], (B, S, Kh, D))
    out = flash_attention(q, k, v, softcap=50.0)
    ref = flash_attention_ref(q, k, v, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_blockwise_matches_full_attention():
    """The pure-JAX blockwise (flash-style) path matches the reference."""
    from repro.models.layers import blockwise_attention, full_attention
    ks = jax.random.split(KEY, 3)
    B, S, H, Kh, D = 2, 4096, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Kh, D))
    v = jax.random.normal(ks[2], (B, S, Kh, D))
    out = blockwise_attention(q, k, v, causal=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# -- packed (segment-masked) prefill ------------------------------------------

def _packed_segments(key, B, S, P, n_segs):
    """Random ragged packing: up to n_segs page-aligned segments per row,
    -1 padded tail."""
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 1 << 30)))
    seg = np.full((B, S), -1, np.int32)
    for b in range(B):
        off = 0
        for s in range(rng.integers(1, n_segs + 1)):
            span = int(rng.integers(1, max(2, (S - off) // P + 1))) * P
            if off + span > S:
                break
            seg[b, off:off + span] = s
            off += span
    return jnp.asarray(seg)


@pytest.mark.parametrize("B,S,P,w", [
    (2, 256, 64, 0),
    (1, 512, 128, 0),
    (2, 256, 64, 128),          # packed + sliding window
    (3, 384, 128, 0),           # non-pow2 grid
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_packed_segments(B, S, P, w, dtype):
    """Segment-masked flash kernel == oracle on ragged packed layouts:
    tokens never attend across segment boundaries, pad (-1) columns
    contribute nothing to real rows."""
    ks = jax.random.split(KEY, 4)
    H, Kh, D = 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kh, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kh, D), dtype)
    seg = _packed_segments(ks[3], B, S, P, 4)
    bq = 128 if S % 128 == 0 else 64
    out = flash_attention(q, k, v, seg_ids=seg, block_q=bq, block_k=bq,
                          window=w)
    ref = flash_attention_ref(q, k, v, causal=True, window=w, seg_ids=seg)
    tol = 3e-6 if dtype == jnp.float32 else 3e-2
    real = np.asarray(seg) >= 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(ref, np.float32)[real], atol=tol,
                               rtol=tol)


def test_flash_attention_packed_equals_solo_prefill():
    """Each segment of a packed row attends exactly as the same tokens
    would alone in their own (left-aligned) row — the property the packed
    prefill engine path relies on for token identity."""
    ks = jax.random.split(KEY, 3)
    S, H, Kh, D = 256, 4, 2, 64
    lens = [128, 64, 64]
    q = jax.random.normal(ks[0], (1, S, H, D))
    k = jax.random.normal(ks[1], (1, S, Kh, D))
    v = jax.random.normal(ks[2], (1, S, Kh, D))
    seg = jnp.asarray(np.repeat(np.arange(3), lens)[None, :], jnp.int32)
    packed = flash_attention(q, k, v, seg_ids=seg, block_q=64, block_k=64)
    off = 0
    for n in lens:
        solo = flash_attention_ref(q[:, off:off + n], k[:, off:off + n],
                                   v[:, off:off + n], causal=True)
        np.testing.assert_allclose(np.asarray(packed[:, off:off + n]),
                                   np.asarray(solo), atol=3e-6, rtol=3e-6)
        off += n


# -- fused sampling (streaming LM head) ---------------------------------------

@pytest.mark.parametrize("B,Dm,V,bv", [
    (4, 64, 1000, 128),         # ragged vocab tail
    (2, 128, 4096, 512),
])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_fused_sample_matches_two_pass(B, Dm, V, bv, softcap):
    """Fused matmul+max+logsumexp == materialise-the-logits oracle."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (B, Dm))
    w = jax.random.normal(ks[1], (Dm, V)) / np.sqrt(Dm)
    vals, idx, lse = fused_sample(x, w, block_v=bv, softcap=softcap)
    rv, ri, rl = fused_sample_ref(x, w, softcap=softcap)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl), atol=1e-5,
                               rtol=1e-5)


def test_fused_sample_greedy_identity_on_ties():
    """Exact duplicate maxima across different vocab blocks: the fused
    kernel must return the FIRST occurrence, matching jnp.argmax."""
    Dm, V, bv = 16, 512, 128
    x = jnp.ones((1, Dm))
    w = np.zeros((Dm, V), np.float32)
    w[:, 37] = 1.0          # block 0
    w[:, 300] = 1.0         # identical logit in block 2
    _, idx, _ = fused_sample(x, jnp.asarray(w), block_v=bv)
    logits = jnp.einsum("bd,dv->bv", x, jnp.asarray(w))
    assert int(idx[0, 0]) == int(jnp.argmax(logits[0])) == 37


# -- int8 KV pages ------------------------------------------------------------

def _quantized(pages):
    """Per-(layer, page) int8 pages and their (L, N) scales."""
    L, N = pages.shape[:2]
    q8, sc = quantize_pages_ref(pages.reshape(L * N, *pages.shape[2:]))
    return q8.reshape(pages.shape), sc.reshape(L, N)


@pytest.mark.parametrize("B,H,Kh,D,P,N,nb",
                         PAGED_CASES + PAGED_EDGE_CASES[:2])
def test_paged_decode_int8_matches_dequant_oracle(B, H, Kh, D, P, N, nb):
    """In-kernel dequant (this layer's per-page scales in scalar memory)
    == dequantize the gathered pages, then the fp reference."""
    case = (B, H, Kh, D, P, N, nb)
    q, kp, vp, bt, kv_len, kn, vn = _paged_inputs(KEY, *case,
                                                  lens=_edge_lens(case))
    (kp8, ksc), (vp8, vsc) = _quantized(kp), _quantized(vp)
    for layer in range(kp.shape[0]):
        out = paged_decode_attention(q, kp8, vp8, bt, kv_len, layer, kn, vn,
                                     k_scales=ksc, v_scales=vsc)
        ref = paged_decode_attention_ref(q, kp8, vp8, bt, kv_len, layer, kn,
                                         vn, k_scales=ksc, v_scales=vsc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


def test_paged_decode_int8_error_within_documented_atol():
    """Quantize fp pages -> int8 decode output stays within the
    documented KV_INT8_DECODE_ATOL of the fp decode on the SAME pages.
    This is the tolerance README promises users of kv_quant="int8"."""
    q, kp, vp, bt, kv_len, kn, vn = _paged_inputs(KEY, 4, 8, 2, 64, 128, 9,
                                                  2)
    (kp8, ksc), (vp8, vsc) = _quantized(kp), _quantized(vp)
    out = paged_decode_attention(q, kp8, vp8, bt, kv_len, 1, kn, vn,
                                 k_scales=ksc, v_scales=vsc)
    fp = paged_decode_attention_ref(q, kp, vp, bt, kv_len, 1, kn, vn)
    err = float(jnp.max(jnp.abs(out - fp)))
    assert err < KV_INT8_DECODE_ATOL, err


def test_int8_quantize_roundtrip_properties():
    """Per-page symmetric quantization invariants: all-zero pages are
    exact, scales are per-page (not global), and requantizing with an
    unchanged scale is idempotent on already-quantized cells."""
    ks = jax.random.split(KEY, 1)[0]
    pages = jax.random.normal(ks, (6, 32, 2, 16))
    pages = pages.at[0].set(0.0)
    q8, sc = quantize_pages_ref(pages)
    assert float(jnp.abs(q8[0].astype(jnp.float32)).max()) == 0.0
    assert sc.shape == (6,)
    deq = q8.astype(jnp.float32) * sc[:, None, None, None]
    q8b, _ = quantize_pages_ref(deq)
    np.testing.assert_array_equal(np.asarray(q8b), np.asarray(q8))
