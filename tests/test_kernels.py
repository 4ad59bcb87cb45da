"""Pallas kernel tests: shape/dtype sweeps, assert_allclose vs the ref.py
pure-jnp oracles (interpret mode executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MapperConfig, alexnet_cifar, analyze, build_mapspace,
                        make_spatial_arch)

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_SHAPES = [
    (2, 256, 4, 2, 64), (1, 128, 8, 8, 128), (2, 512, 4, 1, 64),
    (1, 256, 2, 2, 128),
]


@pytest.mark.parametrize("b,s,h,hkv,d", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, s, h, hkv, d, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shapes():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    ref = flash_attention_ref(q, k, v)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
SSD_SHAPES = [
    (2, 128, 4, 8, 2, 16, 32), (1, 256, 2, 64, 1, 128, 128),
    (2, 64, 4, 16, 4, 32, 16), (1, 128, 8, 32, 8, 64, 64),
]


@pytest.mark.parametrize("b,t,h,p,g,n,q", SSD_SHAPES)
def test_ssd_scan_matches_quadratic_ref(b, t, h, p, g, n, q):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.models.ssm import ssd_reference
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bb = jax.random.normal(ks[3], (b, t, g, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, t, g, n)) * 0.3
    y = ssd_scan(x, dt, a, bb, cc, chunk=q, interpret=True)
    ref = ssd_reference(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_kernel_matches_model_chunked_path():
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.models.ssm import ssd_chunk_scan
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, t, h, p, g, n, q = 2, 128, 4, 16, 2, 32, 32
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bb = jax.random.normal(ks[3], (b, t, g, n)) * 0.3
    cc = jax.random.normal(ks[4], (b, t, g, n)) * 0.3
    y1 = ssd_scan(x, dt, a, bb, cc, chunk=q, interpret=True)
    y2 = ssd_chunk_scan(x, dt, a, bb, cc, q)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# mapspace eval
# ---------------------------------------------------------------------------
def _mapspaces():
    hw = make_spatial_arch(num_pes=64, rf_words=128, gbuf_words=16 * 1024,
                           bits=16, zero_skip=True)
    tw = analyze(alexnet_cifar(batch_size=4))
    cfg = MapperConfig(max_mappings=400, seed=2, enable_bypass=False)
    for wi in (0, 2, 12, 28):
        ms = build_mapspace(tw.intra[wi], hw, cfg).mappings[:80]
        if ms:
            yield tw.intra[wi].name, ms


def _one_group(ms):
    """`mapspace_eval_multi`'s one-group argument for a mapping list."""
    from repro.core.batch_eval import make_static, pack
    st = make_static(ms[0].hardware, ms[0].workload)
    factors, rank, _ = pack(ms)
    return [(st, factors, rank)]


@pytest.mark.parametrize("name,ms", list(_mapspaces()),
                         ids=lambda x: x if isinstance(x, str) else "")
def test_mapspace_eval_matches_batch_oracle(name, ms):
    from repro.kernels.mapspace_eval.ops import mapspace_eval_multi
    from repro.kernels.mapspace_eval.ref import mapspace_eval_ref
    ck, ek = mapspace_eval_multi(_one_group(ms), block=64, interpret=True)
    cr, er = mapspace_eval_ref(ms)
    np.testing.assert_allclose(ck, cr, rtol=1e-5)
    np.testing.assert_allclose(ek, er, rtol=1e-4)


def test_mapspace_eval_pads_to_block():
    from repro.kernels.mapspace_eval.ops import mapspace_eval_multi
    from repro.kernels.mapspace_eval.ref import mapspace_eval_ref
    name, ms = next(_mapspaces())
    ms = ms[:37]                      # not a block multiple
    ck, ek = mapspace_eval_multi(_one_group(ms), block=32, interpret=True)
    cr, er = mapspace_eval_ref(ms)
    assert ck.shape == (37,)
    np.testing.assert_allclose(ck, cr, rtol=1e-5)
    np.testing.assert_allclose(ek, er, rtol=1e-4)


def _multi_groups():
    """(HwStatic, factors, rank) groups of the first two `_mapspaces`,
    with the mappings each group's rows came from."""
    return [(_one_group(ms)[0], ms) for _, ms in list(_mapspaces())[:2]]


@pytest.mark.parametrize("block", [64, 256])
def test_mapspace_eval_multi_traces_once_per_bucket(block):
    """The multi-architecture call is built once per (chain layout,
    bucket, block): a second row count in the same 256-row bucket reuses
    the program, a larger bucket builds one more.  Every call still
    matches the oracle."""
    from repro.kernels.mapspace_eval import kernel
    from repro.kernels.mapspace_eval.ops import mapspace_eval_multi
    from repro.kernels.mapspace_eval.ref import mapspace_eval_ref
    from repro.obs import Tracer, activate
    (g0, ms0), (g1, ms1) = _multi_groups()
    calls = [([(g0[0], g0[1][:37], g0[2][:37])], [ms0[:37]]),
             ([g0], [ms0]),
             ([g0, g1, g0, g1], [ms0, ms1, ms0, ms1])]
    assert len(ms0) != 37
    assert 256 < 2 * (len(ms0) + len(ms1)) <= 512
    kernel._multi_call.clear_cache()
    tr = Tracer()
    traces = []
    with activate(tr):
        for groups, mss in calls:
            ck, ek = mapspace_eval_multi(groups, block=block,
                                         interpret=True)
            refs = [mapspace_eval_ref(ms) for ms in mss]
            np.testing.assert_allclose(
                ck, np.concatenate([r[0] for r in refs]), rtol=1e-5)
            np.testing.assert_allclose(
                ek, np.concatenate([r[1] for r in refs]), rtol=1e-4)
            traces.append(tr.metrics.snapshot()["counters"]["kernel.traces"])
    assert traces == [1, 1, 2]
