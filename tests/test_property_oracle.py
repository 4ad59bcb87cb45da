"""Hypothesis property tests: analytical evaluator vs brute-force simulator.

Contract (see core/simulator.py):
  * matmul-like workloads (R == S == 1): analytical == simulated exactly;
  * general conv workloads: analytical is an upper bound on simulated words.
Both on spatial-free mappings (fanout-1 hardware), where union == per-tile
semantics are unambiguous.  Also: batch evaluator == scalar evaluator.
"""
import math

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; skipping suite")
from hypothesis import given, settings, strategies as st

from repro.core import (MapperConfig, Workload, build_mapspace,
                        evaluate_mapping, make_fpga_arch, make_spatial_arch)
from repro.core.evaluator import COMPUTE, analyze_activity
from repro.core.simulator import simulate_activity

HW1 = make_spatial_arch(num_pes=1, rf_words=96, gbuf_words=4096, bits=16)

dim = st.integers(min_value=1, max_value=5)
small = st.integers(min_value=1, max_value=3)


def _mappings(wl, seed, n=12):
    cfg = MapperConfig(max_mappings=150, seed=seed)
    return build_mapspace(wl, HW1, cfg).mappings[:n]


def _compare(wl, seed, exact):
    for m in _mappings(wl, seed):
        act = analyze_activity(m)
        sim = simulate_activity(m)
        for p in act.pairs:
            s = sim[(p.tensor, p.child)]
            ana_dn = p.parent_read if p.tensor != "output" else p.parent_read
            ana_up = p.parent_write
            if exact:
                assert ana_dn == pytest.approx(s["down_words"]), (
                    wl, p.tensor, p.child, m.factors, m.orders)
                assert ana_up == pytest.approx(s["up_words"])
            else:
                assert ana_dn >= s["down_words"] - 1e-6, (
                    wl, p.tensor, p.child, m.factors, m.orders)
                assert ana_up >= s["up_words"] - 1e-6


@settings(max_examples=25, deadline=None)
@given(n=dim, m=dim, c=dim, e=dim, f=dim, u=small, v=small,
       seed=st.integers(0, 10))
def test_matmul_like_exact(n, m, c, e, f, u, v, seed):
    wl = Workload(dims=(n, m, c, 1, 1, e, f), stride=(u, v))
    _compare(wl, seed, exact=True)


@settings(max_examples=25, deadline=None)
@given(n=small, m=small, c=small, r=st.integers(2, 3), s=st.integers(1, 3),
       e=dim, f=dim, u=small, v=small, dr=small, ds=small,
       seed=st.integers(0, 10))
def test_conv_upper_bound(n, m, c, r, s, e, f, u, v, dr, ds, seed):
    wl = Workload(dims=(n, m, c, r, s, e, f), stride=(u, v),
                  dilation=(dr, ds))
    _compare(wl, seed, exact=False)


@settings(max_examples=10, deadline=None)
@given(c=dim, k=st.integers(1, 3), e=dim, f=dim, seed=st.integers(0, 5))
def test_pool_upper_bound(c, k, e, f, seed):
    wl = Workload(dims=(2, 1, c, k, k, e, f), depthwise=True,
                  kind="pool_max")
    _compare(wl, seed, exact=(k == 1))


def _jnp_model(groups):
    """Score mapping lists (one per design/workload pair, all sharing a
    BatchSig) in ONE `evaluate_batch_multi` call -> (cycles, energy)."""
    from repro.core.batch_eval import (evaluate_batch_multi, make_static,
                                       pack, params_of, sig_of)
    sts = [make_static(ms[0].hardware, ms[0].workload) for ms in groups]
    assert len({sig_of(s) for s in sts}) == 1
    packs = [pack(ms) for ms in groups]
    per = [params_of(s, len(ms)) for s, ms in zip(sts, groups)]
    params = {k: np.concatenate([p[k] for p in per]) for k in per[0]}
    f_, r_, s_ = (np.concatenate(a) for a in zip(*packs))
    out = evaluate_batch_multi(sig_of(sts[0]), params, f_, r_, s_)
    return np.asarray(out["cycles"]), np.asarray(out["energy_pj"])


def _assert_matches_scalar(groups):
    ms = [m for g in groups for m in g]
    scalar = np.array([[evaluate_mapping(m).cycles,
                        evaluate_mapping(m).energy_pj] for m in ms])
    cycles, energy = _jnp_model(groups)
    np.testing.assert_allclose(cycles, scalar[:, 0], rtol=1e-5)
    np.testing.assert_allclose(energy, scalar[:, 1], rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=dim, m=dim, c=dim, r=small, e=dim, zf=st.floats(0, 0.9),
       seed=st.integers(0, 5))
def test_batch_eval_matches_scalar(n, m, c, r, e, zf, seed):
    wl = Workload(dims=(n, m, c, r, 1, e, 1), input_zero_frac=zf)
    hw = make_spatial_arch(num_pes=4, rf_words=64, gbuf_words=1024,
                           bits=16, zero_skip=True)
    ms = build_mapspace(wl, hw, MapperConfig(max_mappings=120,
                                             seed=seed)).mappings[:40]
    if not ms:
        return
    _assert_matches_scalar([ms])


def _case_mapspaces(case):
    """Mapping lists of a workload class the hypothesis test never draws:
    strided/dilated 2-D convs under bypass, FC layers with weight zeros,
    depthwise pooling, the FPGA level layout, and two designs sharing a
    BatchSig in one call."""
    zs_hw = make_spatial_arch(num_pes=16, rf_words=64, gbuf_words=4096,
                              bits=16, zero_skip=True)
    conv = Workload(dims=(2, 4, 3, 3, 3, 4, 4), stride=(2, 2),
                    dilation=(1, 2), input_zero_frac=0.3)
    fc = Workload(dims=(4, 8, 16, 1, 1, 1, 1), weight_zero_frac=0.25)
    pool = Workload(dims=(2, 1, 4, 3, 3, 3, 3), stride=(2, 2),
                    depthwise=True, kind="pool_max")
    fpga = make_fpga_arch(name="fpga", num_pes=16, cache_kb=4.0)
    other = make_spatial_arch(num_pes=64, rf_words=128, gbuf_words=8192,
                              bits=16)
    pairs = {"conv_bypass": [(conv, zs_hw)], "fc": [(fc, zs_hw)],
             "depthwise_pool": [(pool, zs_hw)], "fpga_arch": [(conv, fpga)],
             "mixed_group": [(conv, zs_hw), (fc, other)]}[case]
    out = []
    for i, (wl, hw) in enumerate(pairs):
        cfg = MapperConfig(max_mappings=200, seed=i)
        out.append(build_mapspace(wl, hw, cfg).mappings[:48])
    assert all(out)
    return out


@pytest.mark.parametrize("case", ["conv_bypass", "fc", "depthwise_pool",
                                  "fpga_arch", "mixed_group"])
def test_batch_eval_matches_scalar_cases(case):
    groups = _case_mapspaces(case)
    if case == "conv_bypass":
        assert any(any(m.bypass) for m in groups[0])
    _assert_matches_scalar(groups)


@settings(max_examples=20, deadline=None)
@given(bound=st.integers(1, 36), levels=st.integers(1, 4))
def test_factorizations_complete_and_exact(bound, levels):
    from repro.core.mapper import ordered_factorizations
    fs = ordered_factorizations(bound, levels)
    assert len(set(fs)) == len(fs)
    for f in fs:
        assert math.prod(f) == bound
    # completeness: count equals product over prime powers of
    # C(exp + levels - 1, levels - 1)
    n, total = bound, 1
    p = 2
    while n > 1:
        if p * p > n:
            p = n
        if n % p == 0:
            exp = 0
            while n % p == 0:
                exp += 1
                n //= p
            total *= math.comb(exp + levels - 1, levels - 1)
        p += 1
    assert len(fs) == total
