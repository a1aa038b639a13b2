"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.minplus.ops import minplus
from repro.kernels.minplus.ref import minplus_ref
from repro.kernels.segment_reduce.ops import segment_reduce
from repro.kernels.segment_reduce.ref import segment_reduce_ref
from repro.kernels.topk_compress.ops import decompress, topk_compress
from repro.kernels.topk_compress.ref import topk_compress_ref
from repro.core.soar import minplus as minplus_numpy


# ---------------------------------------------------------------------------
# minplus
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,k", [(1, 4), (7, 33), (64, 128), (130, 17)])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_minplus_shapes(rows, k, dtype):
    rng = np.random.default_rng(rows * 1000 + k)
    a = rng.uniform(0, 50, (rows, k)).astype(dtype)
    b = rng.uniform(0, 50, (rows, k)).astype(dtype)
    got = np.asarray(minplus(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(minplus_ref(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_minplus_with_infs_matches_soar_reference():
    """Oracle chain: pallas == jnp ref == the numpy DP helper in core.soar."""
    rng = np.random.default_rng(0)
    a = rng.uniform(0, 9, (5, 12))
    b = rng.uniform(0, 9, (5, 12))
    a[:, 7:] = np.inf  # capped / infeasible budget entries
    want = minplus_numpy(a, b, out_w=12)  # numpy reference from the DP
    got = np.asarray(minplus(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("rows,k", [(1, 1), (9, 7), (70, 33)])
def test_minplus_engine_fused_path_matches_ref(rows, k):
    """The engine's fused jnp shift-reduction == the quadratic jnp oracle
    (including BIG-sentinel entries, the engine's finite stand-in for inf)."""
    from repro.engine.batched import BIG, _minplus_fused
    rng = np.random.default_rng(rows * 13 + k)
    a = rng.uniform(0, 50, (rows, k)).astype(np.float32)
    b = rng.uniform(0, 50, (rows, k)).astype(np.float32)
    a[rng.random((rows, k)) < 0.2] = BIG
    b[rng.random((rows, k)) < 0.2] = BIG
    got = np.asarray(_minplus_fused(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(minplus_ref(jnp.asarray(a), jnp.asarray(b)))
    # entries involving BIG are saturated garbage by design; compare the
    # real-valued region exactly and the rest only for finiteness
    realish = want < BIG
    np.testing.assert_allclose(got[realish], want[realish], rtol=1e-6)
    assert np.isfinite(got).all()
    assert (got[~realish] >= BIG * 0.999).all()


# ---------------------------------------------------------------------------
# level fold: budget-major Pallas kernel == fused jnp fold, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,C,W,max_c,nl,kcap,budget", [
    (2, 7, 3, 1, 2, 3, None),            # one child: seed + epilogue only
    (3, 9, 4, 3, 4, 1, None),            # budget width 1: no shift window
    (4, 40, 20, 2, 5, 17, None),         # one tile, whole level
    (3, 700, 1000, 2, 3, 40, None),      # several parent-column tiles
    (2, 30, 12, 3, 6, 9, 1),             # one barrier row per tile
])
def test_level_fold_pallas_matches_jnp(monkeypatch, B, C, W, max_c, nl,
                                       kcap, budget):
    from repro.core.tropical import BIG
    from repro.kernels.minplus import levelfold as lf
    if budget is not None:
        monkeypatch.setattr(lf, "VMEM_BUDGET", budget)
    ts, lt, pr, _ = lf._tiles(B * W, nl, kcap)
    assert (budget is None) == (lt == nl)
    rng = np.random.default_rng(B * 1000 + W)
    xs = np.sort(rng.integers(0, 60, (B, C, nl, kcap)), axis=-1)[..., ::-1]
    xs = xs.astype(np.float32)
    xs[rng.random(xs.shape) < 0.1] = 3 * BIG     # entries past BIG exist
    xs[:, -1] = 0.0                              # min-plus identity slot
    xb = np.ascontiguousarray(xs[:, :, 0])
    kid = rng.integers(0, C, (B, W, max_c)).astype(np.int32)
    load = rng.integers(0, 5, (B, W)).astype(np.float32)
    send = rng.integers(0, 3, (B, W)).astype(np.float32)
    avail = rng.random((B, W)) < 0.7
    rho = rng.integers(1, 4, (B, W, nl)).astype(np.float32)
    rho[:, :, 0] = BIG                           # invalid barrier row
    args = (xs, xb, kid, load, send, avail, rho)
    want = lf.level_fold_jnp(*args, nl=nl, kcap=kcap)
    got = lf.level_fold_pallas(*args, nl=nl, kcap=kcap, interpret=True)
    assert got.shape == (B, W, nl, kcap)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


# ---------------------------------------------------------------------------
# segment_reduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("g,c,d", [(1, 1, 8), (4, 7, 130), (16, 32, 512),
                                   (3, 5, 1000)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_segment_reduce(g, c, d, dtype):
    rng = np.random.default_rng(g * 100 + c)
    x = jnp.asarray(rng.normal(size=(g, c, d)), dtype)
    mask = jnp.asarray(rng.random((g, c)) < 0.7)
    got = segment_reduce(x, mask)
    want = segment_reduce_ref(x, mask)
    # float32 tolerance admits summation-order noise on long segments
    # (c=32 rows: kernel accumulates in a different order than the oracle)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == "bfloat16" else 2e-5,
                               atol=1e-2 if dtype == "bfloat16" else 1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,t,d", [(2, 64, 32), (4, 128, 64), (1, 200, 128),
                                    (3, 256, 16)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_causal(bh, t, d, dtype):
    rng = np.random.default_rng(bh * 31 + t)
    q = jnp.asarray(rng.normal(size=(bh, t, d)), dtype)
    k = jnp.asarray(rng.normal(size=(bh, t, d)), dtype)
    v = jnp.asarray(rng.normal(size=(bh, t, d)), dtype)
    got = flash_attention(q, k, v, causal=True)
    want = flash_attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_bidirectional():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=False)
    want = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# topk compress
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,d,k", [(1, 16, 4), (8, 256, 32), (5, 100, 10)])
def test_topk_values_match(r, d, k):
    rng = np.random.default_rng(r * 7 + d)
    x = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    vals, idx = topk_compress(x, k)
    rvals, ridx = topk_compress_ref(x, k)
    # identical index sets & values (deterministic tie-break)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), axis=1),
                                  np.sort(np.asarray(ridx), axis=1))
    np.testing.assert_allclose(
        np.sort(np.abs(np.asarray(vals)), axis=1),
        np.sort(np.abs(np.asarray(rvals)), axis=1), rtol=1e-6)


def test_topk_roundtrip_preserves_topk_energy():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    vals, idx = topk_compress(x, 16)
    dense = decompress(vals, idx, 64)
    # each kept coordinate matches, others zero
    kept = np.zeros((4, 64), bool)
    kept[np.arange(4)[:, None], np.asarray(idx)] = True
    np.testing.assert_allclose(np.asarray(dense)[kept],
                               np.asarray(x)[kept], rtol=1e-6)
    assert np.all(np.asarray(dense)[~kept] == 0)


# ---------------------------------------------------------------------------
# ssm_scan: chunked selective-SSM scan (the §Perf hymba hot path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d,n,chunk", [
    (1, 16, 8, 4, 8), (2, 32, 16, 4, 8), (3, 64, 24, 8, 16),
    (2, 32, 16, 4, 32),
])
def test_ssm_chunk_scan_matches_ref(b, t, d, n, chunk):
    from repro.kernels.ssm_scan import ssm_chunk_scan
    from repro.kernels.ssm_scan.ref import ssm_chunk_scan_ref
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + t), 6)
    u = jax.random.normal(ks[0], (b, t, d))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, 1)) - 2)
    bv = jax.random.normal(ks[2], (b, t, n))
    cv = jax.random.normal(ks[3], (b, t, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.3)
    s0 = jax.random.normal(ks[5], (b, d, n))
    y_ref, s_ref = ssm_chunk_scan_ref(u, delta, bv, cv, a, s0)
    y, s = ssm_chunk_scan(u, delta, bv, cv, a, s0, chunk=chunk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


def test_ssm_chunk_scan_matches_model_forward():
    """Kernel == models/ssm.py chunkwise forward on the same weights."""
    from repro.configs import ARCHS
    from repro.kernels.ssm_scan import ssm_chunk_scan
    from repro.models import ssm as mssm
    cfg = ARCHS["hymba-1.5b"].reduced(chunk_size=8)
    p = mssm.init_mamba(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_model, st = mssm.mamba_forward(p, x, cfg)
    # reproduce the pre-scan projections, then run the kernel for the scan
    u, z = jnp.split(x @ p["w_in"], 2, axis=-1)
    bcdt = (u @ p["w_bcdt"]).astype(jnp.float32)
    N = cfg.ssm_state
    bv, cv = bcdt[..., :N], bcdt[..., N:2 * N]
    delta = jax.nn.softplus(bcdt[..., -1:] + p["dt_bias"][None, None, :1])
    a = -jnp.exp(p["a_log"])
    s0 = jnp.zeros((2, u.shape[-1], N))
    y, s_f = ssm_chunk_scan(u.astype(jnp.float32), delta, bv, cv, a, s0,
                            chunk=8, interpret=True)
    y = y + p["d_skip"] * u.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y.astype(x.dtype) @ p["w_out"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_model),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(st["s"]),
                               rtol=2e-4, atol=2e-5)
