"""Array-native mapspace pipeline (core/mapspace_array.py) and its
plumbing: bit-exact parity with the object path (candidate set, validity,
pruning, survivors, winners), packed scoring through every backend, the
multi-arch Pallas kernel, fused-frontier kernel grouping (one call per
BatchSig group), round_size auto-tuning, the cross-process GC lock, and
the candidate sampler against its frozen copy in `bench/reference`."""
import dataclasses
import functools
import json
import math
import os
import random
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (Conv2D, FC, MapperConfig, PackedMapspace,
                        TaskDescription, Workload, analyze, alexnet_cifar,
                        build_mapspace, build_packed_mapspace,
                        make_fpga_arch, make_spatial_arch)
from repro.core.batch_eval import (batch_best_index, batch_scores,
                                   bucket, evaluate_batch_multi,
                                   make_static, pack, params_of, sig_of)
from repro.core.backend import score_mapspace, best_index
from repro.core.mapper import mapspace_tables, sample_index_rows
from repro.search import (MapspaceJob, ResultCache, cache_key, fused_best,
                          per_arch_best, run_search)
from repro.search.cache import CACHE_FORMAT, GC_LOCK
from repro.search.driver import auto_round_size
from repro.search.space import ArchSpace

TW = analyze(alexnet_cifar(batch_size=4))
HW = make_spatial_arch(num_pes=64, rf_words=128, gbuf_words=16 * 1024,
                       bits=16, zero_skip=True)
FPGA = make_fpga_arch(name="f", num_pes=8, cache_kb=20)


def _assert_parity(wl, hw, cfg):
    obj = build_mapspace(wl, hw, cfg)
    pm = build_packed_mapspace(wl, hw, cfg)
    assert pm.total_candidates == obj.total_candidates
    assert pm.n_valid == obj.n_valid
    assert len(pm) == len(obj.mappings)
    f, r, s = pack(obj.mappings)
    np.testing.assert_array_equal(pm.factors, np.asarray(f))
    np.testing.assert_array_equal(pm.rank, np.asarray(r))
    np.testing.assert_array_equal(pm.store, np.asarray(s))
    for i in {0, len(pm) // 2, len(pm) - 1}:
        m, mo = pm.materialize(i), obj.mappings[i]
        assert m.factors == mo.factors
        assert m.orders == mo.orders
        assert m.bypass == mo.bypass
    return pm, obj


# ---------------------------------------------------------------------------
# construction / validation / pruning parity with the object path
# ---------------------------------------------------------------------------
CASES = [
    ("conv_bypass_sampled", 2, HW,
     dict(max_mappings=300, seed=2, enable_bypass=True)),
    ("conv_nobypass", 2, HW,
     dict(max_mappings=300, seed=2, enable_bypass=False)),
    ("conv_pe_pruned", 2, HW,
     dict(max_mappings=400, seed=7, pe_utilization_min=0.75)),
    ("conv_innermem_pruned", 2, HW,
     dict(max_mappings=400, seed=4, innermem_utilization_min=0.5)),
    ("first_layer_act_reserve", 0, HW,
     dict(max_mappings=300, seed=1, act_reserve={"Gbuf": 1000.0})),
    ("fc", 28, HW, dict(max_mappings=300, seed=5)),
    ("random_orders", 2, HW,
     dict(max_mappings=250, seed=3, n_random_orders=2)),
]


@pytest.mark.parametrize("name,wi,hw,kw", CASES, ids=[c[0] for c in CASES])
def test_packed_matches_object_path(name, wi, hw, kw):
    pm, _ = _assert_parity(TW.intra[wi], hw, MapperConfig(**kw))
    assert len(pm) > 0


def test_packed_enumeration_path():
    # tiny workload on the 3-level FPGA template -> full enumeration
    wl = Workload(dims=(2, 2, 1, 1, 1, 2, 1))
    cfg = MapperConfig(max_mappings=60000, seed=0)
    pm, _ = _assert_parity(wl, FPGA, cfg)
    assert pm.total_candidates <= cfg.max_mappings     # enumerated exactly
    assert pm.n_valid <= pm.total_candidates


def test_packed_depthwise_pool():
    pool = [w for w in TW.intra if not w.has_weight][0]
    _assert_parity(pool, HW, MapperConfig(max_mappings=300, seed=3))


def test_packed_eligibility_and_digest():
    cfg = MapperConfig(max_mappings=200, seed=2, enable_bypass=True)
    pm = build_packed_mapspace(TW.intra[2], HW, cfg)
    mats = pm.materialize_all()
    want = np.asarray([all(not b for b in m.bypass) for m in mats])
    np.testing.assert_array_equal(pm.eligible, want)
    # digest: deterministic, sensitive to content
    pm2 = build_packed_mapspace(TW.intra[2], HW, cfg)
    assert pm.digest() == pm2.digest()
    pm3 = build_packed_mapspace(
        TW.intra[2], HW, MapperConfig(max_mappings=200, seed=9))
    assert pm.digest() != pm3.digest()


def test_run_search_winners_identical_either_pipeline():
    # both pipelines must elect bit-identical winners (acceptance gate)
    task = TaskDescription(
        name="tiny", input_shape=(8, 8, 3), batch_size=2,
        processing_type="Inference",
        layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
                FC(10, name="fc")))
    space = ArchSpace.spatial(num_pes=(16, 64), rf_words=(64,),
                              gbuf_words=(2048, 8192), bits=16)
    cfg = MapperConfig(max_mappings=200, seed=0)
    rep = run_search(task, space, goal="edp", cfg=cfg, use_packed=False)
    ref = run_search(task, space, goal="edp", cfg=cfg, use_packed=True)
    assert rep.best.hardware.name == ref.best.hardware.name
    assert rep.goal_value() == ref.goal_value()
    for ra, rb in zip(rep.best.per_workload, ref.best.per_workload):
        assert ra.mapping.factors == rb.mapping.factors
        assert ra.mapping.orders == rb.mapping.orders
        assert ra.mapping.bypass == rb.mapping.bypass


# ---------------------------------------------------------------------------
# hypothesis property test: parity across random hardware/workload draws
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(1, 4), m=st.integers(1, 6), c=st.integers(1, 4),
        rs=st.integers(1, 3), e=st.integers(1, 4), f=st.integers(1, 4),
        seed=st.integers(0, 6), num_pes=st.sampled_from([4, 16]),
        rf=st.sampled_from([64, 128]),
        gbuf=st.sampled_from([2048, 8192]),
        zero_skip=st.booleans(), bypass=st.booleans(),
        pe_min=st.sampled_from([0.0, 0.75]))
    def test_packed_parity_property(n, m, c, rs, e, f, seed, num_pes, rf,
                                    gbuf, zero_skip, bypass, pe_min):
        wl = Workload(dims=(n, m, c, rs, rs, e, f))
        hw = make_spatial_arch(num_pes=num_pes, rf_words=rf,
                               gbuf_words=gbuf, bits=16,
                               zero_skip=zero_skip)
        cfg = MapperConfig(max_mappings=150, seed=seed,
                           enable_bypass=bypass, pe_utilization_min=pe_min)
        pm, obj = _assert_parity(wl, hw, cfg)
        # same winner under the batch scorer
        if len(pm) >= 1:
            assert batch_best_index(pm, "edp") == \
                batch_best_index(obj.mappings, "edp")


# ---------------------------------------------------------------------------
# packed scoring through the backend dispatch
# ---------------------------------------------------------------------------
def _packed_and_objects(wi=2, bypass=False, seed=2, n=300):
    cfg = MapperConfig(max_mappings=n, seed=seed, enable_bypass=bypass)
    pm = build_packed_mapspace(TW.intra[wi], HW, cfg)
    return pm, pm.materialize_all()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_score_mapspace_packed_equals_objects(backend):
    pm, ms = _packed_and_objects(bypass=True)
    sp, vp = score_mapspace(pm, "edp", backend, interpret=True)
    so, vo = score_mapspace(ms, "edp", backend, interpret=True)
    np.testing.assert_array_equal(vp, vo)
    np.testing.assert_array_equal(sp, so)
    assert best_index(pm, "edp", backend, interpret=True) == \
        best_index(ms, "edp", backend, interpret=True)


def test_batch_scores_accepts_packed():
    pm, ms = _packed_and_objects()
    sp, vp = batch_scores(pm, "edp")
    so, vo = batch_scores(ms, "edp")
    np.testing.assert_array_equal(sp, so)
    np.testing.assert_array_equal(vp, vo)
    assert batch_best_index(pm, "edp") == batch_best_index(ms, "edp")


# ---------------------------------------------------------------------------
# multi-arch kernel: parity with evaluate_batch_multi + one call per group
# ---------------------------------------------------------------------------
def _kernel_jobs(n_jobs=3, bypass=False):
    archs = [make_spatial_arch(num_pes=p, rf_words=r, gbuf_words=g,
                               bits=16, zero_skip=zs)
             for p, r, g, zs in ((64, 128, 16 * 1024, True),
                                 (128, 256, 32 * 1024, False),
                                 (32, 64, 8 * 1024, True))][:n_jobs]
    wls = [TW.intra[2], TW.intra[12], TW.intra[28]][:n_jobs]
    jobs = []
    for i, (hw, wl) in enumerate(zip(archs, wls)):
        cfg = MapperConfig(max_mappings=200, seed=i, enable_bypass=bypass)
        jobs.append(MapspaceJob(tag=i, hw=hw, workload=wl,
                                packed=build_packed_mapspace(wl, hw, cfg)))
    return jobs


def test_multi_arch_kernel_matches_evaluate_batch_multi():
    import jax.numpy as jnp
    from repro.kernels.mapspace_eval.ops import mapspace_eval_multi
    jobs = _kernel_jobs()
    groups = [(j.packed.static, j.packed.factors, j.packed.rank)
              for j in jobs]
    assert len({sig_of(g[0]) for g in groups}) == 1
    cm, em = mapspace_eval_multi(groups, block=64, interpret=True)
    factors = np.concatenate([g[1] for g in groups])
    rank = np.concatenate([g[2] for g in groups])
    store = np.concatenate([j.packed.store for j in jobs])
    params = {}
    per = [params_of(g[0], g[1].shape[0]) for g in groups]
    for k in per[0]:
        params[k] = np.concatenate([p[k] for p in per])
    n = factors.shape[0]
    pad = bucket(n) - n
    if pad:
        rep = lambda a: np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
        factors, rank, store = rep(factors), rep(rank), rep(store)
        params = {k: rep(v) for k, v in params.items()}
    res = evaluate_batch_multi(
        sig_of(groups[0][0]),
        {k: jnp.asarray(v) for k, v in params.items()},
        jnp.asarray(factors), jnp.asarray(rank), jnp.asarray(store))
    np.testing.assert_allclose(cm, np.asarray(res["cycles"][:n]),
                               rtol=2e-4)
    np.testing.assert_allclose(em, np.asarray(res["energy_pj"][:n]),
                               rtol=2e-4)


def test_fused_best_issues_one_kernel_call_per_sig_group(monkeypatch):
    from repro.kernels.mapspace_eval import ops as kops
    jobs = _kernel_jobs()
    calls = []
    orig = kops.mapspace_eval_multi

    def probe(groups, **kw):
        calls.append(len(groups))
        return orig(groups, **kw)

    monkeypatch.setattr(kops, "mapspace_eval_multi", probe)
    got = fused_best(jobs, "edp", backend="pallas")
    assert calls == [len(jobs)]          # ONE call, all jobs fused
    ref = fused_best(jobs, "edp", backend="jnp")
    assert [(b.tag, b.index) for b in got] == \
        [(b.tag, b.index) for b in ref]


def test_fused_best_packed_mixed_eligibility():
    # bypass mapspaces fall back to the fused jnp groups; winners agree
    jobs = _kernel_jobs(bypass=True) + _kernel_jobs(n_jobs=1)
    ref = fused_best(jobs, "edp", backend="jnp")
    got = fused_best(jobs, "edp", backend="pallas")
    assert [(b.tag, b.index) for b in got] == \
        [(b.tag, b.index) for b in ref]


def test_per_arch_best_packed_matches_objects():
    jobs_p = _kernel_jobs()
    jobs_o = [MapspaceJob(tag=j.tag, hw=j.hw, workload=j.workload,
                          mappings=j.packed.materialize_all())
              for j in jobs_p]
    a = per_arch_best(jobs_p, "edp")
    b = per_arch_best(jobs_o, "edp")
    assert [(x.tag, x.index, x.n_scored) for x in a] == \
        [(x.tag, x.index, x.n_scored) for x in b]


# ---------------------------------------------------------------------------
# round_size auto-tuning
# ---------------------------------------------------------------------------
def test_auto_round_size_scaling():
    assert auto_round_size(0) is None            # no signal yet
    assert auto_round_size(100) == 64            # small mapspaces: fuse big
    assert auto_round_size(20000) == 3           # large: stay small
    assert auto_round_size(10 ** 7) == 2         # floor
    big = auto_round_size(1)
    assert big == 64                             # ceiling


def test_run_search_round_size_auto():
    task = TaskDescription(
        name="tiny", input_shape=(8, 8, 3), batch_size=2,
        processing_type="Inference",
        layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
                FC(10, name="fc")))
    space = ArchSpace.spatial(num_pes=(16, 64), rf_words=(64,),
                              gbuf_words=(2048, 8192), bits=16)
    cfg = MapperConfig(max_mappings=200, seed=0)
    auto = run_search(task, space, goal="edp", cfg=cfg, round_size="auto")
    fixed = run_search(task, space, goal="edp", cfg=cfg, round_size=8)
    assert auto.best.hardware.name == fixed.best.hardware.name
    assert auto.goal_value() == fixed.goal_value()
    assert auto.n_evaluated == fixed.n_evaluated
    with pytest.raises(ValueError):
        run_search(task, space, goal="edp", cfg=cfg, round_size="huge")
    with pytest.raises(ValueError):
        run_search(task, space, goal="edp", cfg=cfg, round_size=0)


# ---------------------------------------------------------------------------
# digest participates in the cache key
# ---------------------------------------------------------------------------
def test_cache_key_mapspace_digest_component():
    wl, hw, cfg = TW.intra[2], HW, MapperConfig(max_mappings=100)
    base = cache_key(wl, hw, cfg, "edp")
    d1 = cache_key(wl, hw, cfg, "edp", mapspace="abc")
    d2 = cache_key(wl, hw, cfg, "edp", mapspace="def")
    assert len({base, d1, d2}) == 3
    assert d1 == cache_key(wl, hw, cfg, "edp", mapspace="abc")


# ---------------------------------------------------------------------------
# cross-process GC lock
# ---------------------------------------------------------------------------
def _fill(cache, n):
    for i in range(n):
        cache.put(f"k{i:04d}", {"v": CACHE_FORMAT, "i": i})
        os.utime(os.path.join(cache.path, f"k{i:04d}.json"),
                 (i + 1, i + 1))


def _disk_keys(path):
    return sorted(f[:-5] for f in os.listdir(path) if f.endswith(".json"))


def test_gc_skipped_while_lock_held(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=4,
                    max_disk_bytes=None, gc_every=10_000)
    _fill(c, 10)
    lock = tmp_path / GC_LOCK
    lock.write_text("12345")             # a live holder
    assert c.gc() == 0                   # skipped, nothing evicted
    assert len(_disk_keys(c.path)) == 10
    lock.unlink()
    assert c.gc() == 6                   # lock free: bound enforced
    assert not (tmp_path / GC_LOCK).exists()    # released


def test_gc_breaks_stale_lock(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=4,
                    max_disk_bytes=None, gc_every=10_000)
    _fill(c, 10)
    lock = tmp_path / GC_LOCK
    lock.write_text("999")
    os.utime(lock, (1, 1))               # ancient: a dead process's lock
    assert c.gc() == 6                   # broken and retaken
    assert not lock.exists()


def test_two_result_caches_one_directory(tmp_path):
    c1 = ResultCache(path=str(tmp_path), max_disk_entries=8,
                     max_disk_bytes=None, gc_every=10_000)
    c2 = ResultCache(path=str(tmp_path), max_disk_entries=8,
                     max_disk_bytes=None, gc_every=10_000)
    for i in range(20):                  # interleaved writers
        (c1 if i % 2 == 0 else c2).put(f"k{i:04d}", {"v": CACHE_FORMAT, "i": i})
    e1 = c1.gc()
    e2 = c2.gc()
    assert e1 + e2 >= 12                 # bound enforced exactly once each
    keys = _disk_keys(str(tmp_path))
    assert len(keys) <= 8
    # every survivor is readable, untorn, from a *fresh* instance
    c3 = ResultCache(path=str(tmp_path))
    for k in keys:
        assert c3.get(k) is not None
    assert not (tmp_path / GC_LOCK).exists()


def test_pack_phase_splits_into_build_steps():
    from repro.obs import Tracer, activate
    cfg = MapperConfig(max_mappings=300, seed=1)
    tr = Tracer()
    with activate(tr):
        pm = build_packed_mapspace(TW.intra[2], HW, cfg)
    spans = tr.buffer.snapshot()
    pack_ = next(s for s in spans if s.name == "pack")
    kids = [s for s in spans if s.parent == pack_.index]
    assert [s.name for s in kids] == ["pack.tables", "pack.rows",
                                      "pack.assemble"]
    assert kids[1].attrs["rows"] == kids[2].attrs["rows"] \
        == pack_.attrs["candidates"] >= len(pm)
    assert sum(s.duration for s in kids) <= pack_.duration


def test_pack_rows_counts_draws():
    from repro.obs import Tracer, activate
    cfg = MapperConfig(max_mappings=300, seed=1)
    tr = Tracer()
    with activate(tr):
        build_packed_mapspace(TW.intra[2], HW, cfg)
    rows = next(s for s in tr.buffer.snapshot() if s.name == "pack.rows")
    assert rows.attrs["total"] > cfg.max_mappings         # sampled
    assert rows.attrs["drawn"] >= rows.attrs["rows"] == cfg.max_mappings
    assert rows.attrs["batches"] >= 1


# ---------------------------------------------------------------------------
# the sampler against its frozen copy in the benchmark's reference
# ---------------------------------------------------------------------------
_REPO = Path(__file__).resolve().parents[1]
_SEEDS = (0, 3000014001, 2 ** 31 + 7)


def _reference_mapper():
    if str(_REPO) not in sys.path:
        sys.path.insert(0, str(_REPO))
    from bench.reference.trim import mapper
    return mapper


@functools.lru_cache(maxsize=None)
def _bench_tables(name, bypass):
    """A dozen of the 216 distinct mapspaces of one query of a benchmark
    configuration, spread over its designs and workloads."""
    from repro.core.task_analyst import NETWORKS
    config = json.loads((_REPO / "bench" / "configs" / f"{name}.json")
                        .read_text())
    net, lat = config["network"], config["lattice"]
    task = analyze(NETWORKS[net["name"]](batch_size=net["batch_size"],
                                          processing=net["processing"]))
    space = ArchSpace.spatial(num_pes=lat["num_pes"],
                              rf_words=lat["rf_words"],
                              gbuf_words=lat["gbuf_words"], bits=lat["bits"],
                              zero_skip=lat["zero_skip"])
    cfg = MapperConfig(max_mappings=config["max_mappings"],
                       enable_bypass=bypass)
    # mapspace tables read only a workload's dims
    dims = list(dict.fromkeys(w.dims for w in task.intra))
    pairs = [(space.at(c), d) for c in space.all_coords() for d in dims]
    return config["max_mappings"], [
        mapspace_tables(Workload(dims=d), hw, cfg, random.Random(0))
        for hw, d in pairs[::len(pairs) // 12]]


def _no_routing_case():
    hw = dataclasses.replace(
        HW, levels=tuple(lv for lv in HW.levels if lv.kind != "routing"))
    cfg = MapperConfig(max_mappings=500, seed=5)
    tables = mapspace_tables(Workload(dims=(4, 16, 8, 3, 3, 8, 8)), hw, cfg,
                             random.Random(5))
    assert tables.first_rout is None                    # no greedy fill
    return cfg, [tables], lambda stats: None


def _multi_batch_case():
    # total just above the budget: ~one new row per batch at the end
    cfg = MapperConfig(max_mappings=10 ** 9, enable_bypass=False, seed=3)
    tables = mapspace_tables(Workload(dims=(2, 1, 1, 1, 1, 1, 1)), HW, cfg,
                             random.Random(3))
    cfg = dataclasses.replace(cfg, max_mappings=tables.total - 1)

    def check(stats):
        assert stats["batches"] > 1
    return cfg, [tables], check


def _exhausted_case():
    # every order listed twice (as colliding random orders are): fewer
    # distinct rows than the budget, so sampling runs out of draws
    cfg = MapperConfig(max_mappings=10 ** 9, enable_bypass=False, seed=4)
    tables = mapspace_tables(Workload(dims=(2, 1, 1, 1, 1, 1, 1)), HW, cfg,
                             random.Random(4))
    distinct = tables.total
    tables = dataclasses.replace(
        tables, orders=tables.orders * 2,
        canon_order=tables.canon_order * 2,
        total=tables.total * 2 ** len(tables.mem_idx))
    cfg = dataclasses.replace(cfg, max_mappings=distinct + 1)
    assert tables.total > cfg.max_mappings

    def check(stats):
        assert stats["batches"] > 1
        assert stats["drawn"] == 20 * cfg.max_mappings
    return cfg, [tables], check


def _int64_overflow_case():
    # one loop order listed 5040 times (as colliding random orders are):
    # the key's radices overflow int64, while few distinct rows make
    # the dedup work
    cfg = MapperConfig(max_mappings=2000, seed=9)
    tables = mapspace_tables(Workload(dims=(2, 2, 2, 2, 2, 1, 1)), HW, cfg,
                             random.Random(9))
    n, levels = 5040, len(tables.mem_idx)
    tables = dataclasses.replace(
        tables, orders=tables.orders[:1] * n, canon_order=[0] * n,
        total=tables.total // len(tables.orders) ** levels * n ** levels)
    radices = [len(o) for o in tables.per_dim] \
        + [len(tables.orders) + 1] * tables.nl \
        + [len(b) for b in tables.bypass_choices]
    assert math.prod(radices) > np.iinfo(np.int64).max   # bytes key
    assert tables.total > cfg.max_mappings

    def check(stats):
        assert stats["drawn"] > cfg.max_mappings          # duplicates met
    return cfg, [tables], check


def _bench_case(name, bypass, seed):
    def case():
        budget, tables = _bench_tables(name, bypass)
        cfg = MapperConfig(max_mappings=budget, enable_bypass=bypass,
                           seed=seed)
        return cfg, tables, lambda stats: None
    return case


SAMPLER_CASES = {
    f"{name}-{'bypass' if bypass else 'nobypass'}-{seed}":
        _bench_case(name, bypass, seed)
    for name in ("alexnet-cifar-fig20", "resnet18-im-fig20")
    for bypass in (True, False) for seed in _SEEDS}
SAMPLER_CASES.update({"no-routing-level": _no_routing_case,
                      "multi-batch": _multi_batch_case,
                      "exhausts-max-draws": _exhausted_case,
                      "int64-overflow-bytes-key": _int64_overflow_case})


@pytest.mark.parametrize("case", SAMPLER_CASES)
def test_sample_index_rows_matches_frozen_reference(case):
    cfg, tables, check = SAMPLER_CASES[case]()
    reference = _reference_mapper()
    for t in tables:
        stats = {}
        got = sample_index_rows(t, cfg, cfg.seed, stats)
        want = reference.sample_index_rows(t, cfg, cfg.seed)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
        assert stats["drawn"] >= len(got[0])
        check(stats)
