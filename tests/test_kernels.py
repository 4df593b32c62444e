"""Pallas kernel validation: interpret-mode sweeps vs pure-jnp oracles.

``hypothesis`` is an optional dev dependency: when it is not installed this
module is skipped at collection time rather than erroring.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional dev dependency")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_ffn import fused_ffn
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(key, shape, dtype, scale=1.0):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Bkv,G,S,hd,bq,bk", [
    (2, 1, 128, 64, 128, 128),       # MHA, single block
    (1, 4, 256, 64, 128, 128),       # GQA fold
    (2, 2, 512, 128, 128, 256),      # uneven q/k blocks
    (1, 8, 256, 80, 64, 64),         # non-pow2 head dim (llava-ish)
])
def test_flash_attention_sweep(dtype, Bkv, G, S, hd, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (Bkv, G, S, hd), dtype)
    k = _rand(ks[1], (Bkv, S, hd), dtype)
    v = _rand(ks[2], (Bkv, S, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    qf = q.reshape(Bkv * G, S, hd)
    kf = jnp.repeat(k[:, None], G, 1).reshape(Bkv * G, S, hd)
    vf = jnp.repeat(v[:, None], G, 1).reshape(Bkv * G, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf).reshape(out.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [64, 128, 1000])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    Bkv, G, S, hd = 1, 2, 512, 64
    q, k, v = (_rand(kk, s, jnp.float32) for kk, s in zip(
        ks, [(Bkv, G, S, hd), (Bkv, S, hd), (Bkv, S, hd)]))
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    qf = q.reshape(Bkv * G, S, hd)
    kf = jnp.repeat(k[:, None], G, 1).reshape(Bkv * G, S, hd)
    vf = jnp.repeat(v[:, None], G, 1).reshape(Bkv * G, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf, window=window) \
        .reshape(out.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Bkv,G,C,hd,bc", [
    (2, 4, 512, 64, 256),
    (1, 16, 2048, 128, 512),     # starcoder2-like huge GQA fold
    (4, 1, 1024, 64, 128),
])
def test_decode_attention_sweep(dtype, Bkv, G, C, hd, bc):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (Bkv, G, hd), dtype)
    k = _rand(ks[1], (Bkv, C, hd), dtype)
    v = _rand(ks[2], (Bkv, C, hd), dtype)
    lens = jax.random.randint(ks[3], (Bkv, 1), 1, C + 1)
    valid = jnp.arange(C)[None, :] < lens
    out = decode_attention(q, k, v, valid, block_c=bc, interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_attention_ring_mask():
    """Mask pattern of a ring buffer (non-contiguous valid slots)."""
    Bkv, G, C, hd = 2, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (Bkv, G, hd), jnp.float32)
    k = _rand(ks[1], (Bkv, C, hd), jnp.float32)
    v = _rand(ks[2], (Bkv, C, hd), jnp.float32)
    valid = jax.random.bernoulli(ks[3], 0.7, (Bkv, C))
    out = decode_attention(q, k, v, valid, block_c=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ paged decode attention
@pytest.mark.parametrize("G", [2, 4])
@pytest.mark.parametrize("n_bt", [32, 17])       # 17: not a multiple of ppg
def test_paged_decode_attention_live_blocks(G, n_bt):
    """The paged kernel (interpreted) against the gather reference of
    ``ops`` on layer 1 of a shuffled two-layer pool, 8 pages a group.

    Rows: pos 0, bs-1, bs, mid-table, capacity-1, a retired row (all
    sentinel), a row stepping past its 3 blocks, a random one. The
    retired row has no reference (every position masked): it must come
    out zero. A second call with other positions and tables compiles
    nothing new.
    """
    from repro.kernels import ops
    from repro.kernels.decode_attention import (pages_per_group,
                                                paged_decode_attention)

    B, NKV, hd, bs = 8, 8, 128, 16
    cap, P = n_bt * bs, 8 * n_bt
    assert pages_per_group(NKV, bs, hd, 2, n_bt) == 8
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (B, 1, NKV * G, hd), jnp.bfloat16)
    k_pool = _rand(ks[1], (2, P, NKV, bs, hd), jnp.bfloat16)
    v_pool = _rand(ks[2], (2, P, NKV, bs, hd), jnp.bfloat16)
    rng = np.random.default_rng(n_bt * G)

    def case(pos, n_blocks):
        tables = rng.permutation(P).reshape(B, n_bt).astype(np.int32)
        tables[np.arange(n_bt)[None, :] >= n_blocks[:, None]] = P
        args = (q, k_pool, v_pool, jnp.asarray(tables),
                jnp.asarray(pos, jnp.int32))
        got = np.asarray(ops.paged_decode_attention(*args, layer=1),
                         np.float32)
        want = np.asarray(ops.paged_decode_attention(*args, layer=1,
                                                     force_ref=True),
                          np.float32)
        assert np.all(np.isfinite(got))
        live = n_blocks > 0
        np.testing.assert_allclose(got[live], want[live],
                                   **TOL[jnp.bfloat16])
        assert not np.any(got[~live])

    pos = np.array([0, bs - 1, bs, cap // 2 + 5, cap - 1, 37, 5 * bs + 3,
                    int(rng.integers(0, cap))])
    n_blocks = np.minimum(pos // bs + 1, n_bt)
    n_blocks[5], n_blocks[6] = 0, 3
    case(pos, n_blocks)
    compiled = paged_decode_attention._cache_size()

    pos = np.array([3, bs + 1, 2 * bs - 1, cap - 1, cap // 3, 0, 2 * bs,
                    int(rng.integers(0, cap))])
    n_blocks = np.minimum(pos // bs + 1, n_bt)
    n_blocks[1], n_blocks[5], n_blocks[6] = 0, 0, 1
    case(pos, n_blocks)
    assert paged_decode_attention._cache_size() == compiled


# -------------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,S,hd,ds,chunk", [
    (2, 128, 64, 64, 64),
    (4, 256, 32, 16, 128),
    (1, 512, 64, 128, 128),
])
def test_ssd_scan_sweep(dtype, BH, S, hd, ds, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = _rand(ks[0], (BH, S, hd), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (BH, S), jnp.float32))
    a = -jax.nn.softplus(_rand(ks[2], (BH, S), jnp.float32)) * 0.5
    Bm = _rand(ks[3], (BH, S, ds), dtype)
    Cm = _rand(ks[4], (BH, S, ds), dtype)
    y, sf = ssd_scan(x, dt, a, Bm, Cm, chunk=chunk, interpret=True)
    yr, sfr = ref.ssd_scan_ref(x, dt, a, Bm, Cm)
    # long-chain f32 accumulation: compare relative to the output scale
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    tol = (dict(rtol=1e-3, atol=2e-5 * scale) if dtype == jnp.float32
           else dict(rtol=5e-2, atol=5e-2 * scale))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr),
                               rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------------- RWKV scan
@pytest.mark.parametrize("BH,S,hd,chunk", [
    (2, 128, 64, 32),
    (4, 256, 32, 64),
])
def test_rwkv6_scan_sweep(BH, S, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r = _rand(ks[0], (BH, S, hd), jnp.float32, 0.5)
    k = _rand(ks[1], (BH, S, hd), jnp.float32, 0.5)
    v = _rand(ks[2], (BH, S, hd), jnp.float32, 0.5)
    la = -jnp.exp(_rand(ks[3], (BH, S, hd), jnp.float32, 0.3) - 2.0)
    u = _rand(ks[4], (BH, hd), jnp.float32, 0.3)
    y, sf = rwkv6_scan(r, k, v, la, u, chunk=chunk, interpret=True)
    yr, sfr = ref.rwkv_scan_ref(r, k, v, la, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfr),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- fused FFN
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,T,d,f,bt,bf", [
    (1, 256, 128, 512, 128, 256),    # dense MLP shape
    (4, 128, 64, 256, 64, 128),      # small experts
    (2, 128, 128, 1408, 128, 704),   # deepseek-expert-like f
])
def test_fused_ffn_sweep(dtype, E, T, d, f, bt, bf):
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    x = _rand(ks[0], (E, T, d), dtype, 0.5)
    wg = _rand(ks[1], (E, d, f), dtype, 0.1)
    wu = _rand(ks[2], (E, d, f), dtype, 0.1)
    wd = _rand(ks[3], (E, f, d), dtype, 0.1)
    y = fused_ffn(x, wg, wu, wd, block_t=bt, block_f=bf, interpret=True)
    want = ref.fused_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


# --------------------------------------------------------- model-adapter level
def test_ops_flash_matches_model_attention():
    """ops.flash_attention == the model's _sdpa path (same math)."""
    from repro.kernels import ops
    from repro.models import attention as A
    from repro.models.config import ModelConfig

    cfg = ModelConfig(arch_id="t", family="dense", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=64,
                      dtype="float32")
    B, S, hd = 2, 128, cfg.hd
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, S, 4, hd), jnp.float32)
    k = _rand(ks[1], (B, S, 2, hd), jnp.float32)
    v = _rand(ks[2], (B, S, 2, hd), jnp.float32)
    mask = A.causal_mask(cfg, jnp.arange(S), jnp.arange(S))
    want = A._sdpa(cfg, q, k, v, mask)
    got = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- property test
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 128, 256]),
       st.sampled_from([32, 64]), st.integers(0, 2 ** 31 - 1))
def test_flash_attention_property(bkv, s, hd, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (bkv, 2, s, hd), jnp.float32)
    k = _rand(ks[1], (bkv, s, hd), jnp.float32)
    v = _rand(ks[2], (bkv, s, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    # rows are convex combinations of v rows: bounded by v extremes
    assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-4
    assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-4
    # first position attends only to itself
    np.testing.assert_allclose(np.asarray(out[:, :, 0]),
                               np.asarray(jnp.broadcast_to(
                                   v[:, None, 0], out[:, :, 0].shape)),
                               rtol=1e-5, atol=1e-5)
