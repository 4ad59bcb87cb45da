"""Backend dispatch layer (core/backend.py): pallas/jnp parity across every
mapping class, eligibility gating, search-level equivalence, cache-key
separation, and the bounded disk cache tier.

The pallas engine runs in interpret mode here (no TPU in CI) — the same
code path a TPU run compiles, per the kernel's design contract."""
import os

import numpy as np
import pytest

from repro.core import (MapperConfig, TaskDescription, Conv2D, FC,
                        alexnet_cifar, analyze, build_mapspace,
                        make_spatial_arch)
from repro.core.backend import (BACKENDS, best_index, default_backend,
                                eligibility_mask, pallas_eligible,
                                resolve_backend, score_mapspace,
                                validity_mask)
from repro.core.batch_eval import batch_scores
from repro.search import (MapspaceJob, ResultCache, cache_key, fused_best,
                          per_arch_best, run_search)
from repro.search.space import ArchSpace

TW = analyze(alexnet_cifar(batch_size=4))


def _arch(zero_skip=True):
    return make_spatial_arch(num_pes=64, rf_words=128,
                             gbuf_words=16 * 1024, bits=16,
                             zero_skip=zero_skip)


def _mapspace(wi, *, bypass, zero_skip=True, n=60, seed=2):
    hw = _arch(zero_skip)
    cfg = MapperConfig(max_mappings=300, seed=seed, enable_bypass=bypass)
    return build_mapspace(TW.intra[wi], hw, cfg).mappings[:n]


# ---------------------------------------------------------------------------
# parity: every mapping class, pallas (interpret) vs the jnp oracle
# ---------------------------------------------------------------------------
CLASSES = [
    # (id, workload idx, bypass, zero_skip)
    ("conv_sliding_nobypass", 2, False, True),      # R/S/E/F sliding windows
    ("conv_sliding_bypass_mix", 2, True, True),     # bypass rows -> fallback
    ("fc_nobypass", 28, False, True),               # matmul-shaped
    ("conv_no_zeroskip", 2, False, False),          # zs_boundary = -1
    ("first_layer_bypass_mix", 0, True, True),
]


@pytest.mark.parametrize("name,wi,bypass,zs",
                         CLASSES, ids=[c[0] for c in CLASSES])
def test_pallas_backend_matches_jnp_oracle(name, wi, bypass, zs):
    ms = _mapspace(wi, bypass=bypass, zero_skip=zs)
    assert ms, "empty mapspace would vacuously pass"
    sj, vj = score_mapspace(ms, "edp", backend="jnp")
    sp, vp = score_mapspace(ms, "edp", backend="pallas", interpret=True)
    np.testing.assert_array_equal(vp, vj)
    np.testing.assert_allclose(sp, sj, rtol=2e-4)
    if bypass:
        mask = eligibility_mask(ms)
        assert not mask.all(), "bypass class must exercise the fallback"
        assert mask.ndim == 1 and len(mask) == len(ms)


@pytest.mark.parametrize("goal", ["latency", "energy", "edp"])
def test_parity_every_goal(goal):
    ms = _mapspace(2, bypass=False)
    sj, _ = score_mapspace(ms, goal, backend="jnp")
    sp, _ = score_mapspace(ms, goal, backend="pallas", interpret=True)
    np.testing.assert_allclose(sp, sj, rtol=2e-4)


def test_best_index_agrees_across_backends():
    ms = _mapspace(2, bypass=True, n=120)
    assert best_index(ms, "edp", "jnp") == \
        best_index(ms, "edp", "pallas", interpret=True)


def test_validity_mask_matches_oracle():
    ms = _mapspace(2, bypass=True, n=120)
    _, vj = batch_scores(ms, "edp")
    np.testing.assert_array_equal(validity_mask(ms), np.asarray(vj))


# ---------------------------------------------------------------------------
# eligibility + backend resolution
# ---------------------------------------------------------------------------
def test_eligibility_is_no_bypass():
    mixed = _mapspace(2, bypass=True, n=120)
    assert all(pallas_eligible(m) == all(not b for b in m.bypass)
               for m in mixed)
    pure = _mapspace(2, bypass=False)
    assert eligibility_mask(pure).all()


def test_resolve_backend():
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") == default_backend()
    assert default_backend() in ("jnp", "pallas")
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    with pytest.raises(ValueError):
        score_mapspace(_mapspace(2, bypass=False, n=8), "throughput")
    with pytest.raises(ValueError):
        score_mapspace([], "edp")


# ---------------------------------------------------------------------------
# search-level routing: frontier + run_search equivalence
# ---------------------------------------------------------------------------
TASK = TaskDescription(
    name="tiny", input_shape=(8, 8, 3), batch_size=2,
    processing_type="Inference",
    layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
            FC(10, name="fc")))
NB_CFG = MapperConfig(max_mappings=200, seed=0, enable_bypass=False)


def _jobs(bypass_second):
    hw1, hw2 = _arch(), make_spatial_arch(
        num_pes=128, rf_words=128, gbuf_words=32 * 1024, bits=16,
        zero_skip=True)
    j1 = MapspaceJob(tag="a", hw=hw1, workload=TW.intra[2],
                     mappings=_mapspace(2, bypass=False, n=70))
    cfg = MapperConfig(max_mappings=300, seed=2,
                       enable_bypass=bypass_second)
    j2 = MapspaceJob(tag="b", hw=hw2, workload=TW.intra[12],
                     mappings=build_mapspace(TW.intra[12], hw2,
                                             cfg).mappings[:70])
    return [j1, j2]


def test_fused_best_pallas_routes_eligible_jobs():
    jobs = _jobs(bypass_second=True)     # job a kernel-eligible, job b not
    ref = fused_best(jobs, "edp", backend="jnp")
    got = fused_best(jobs, "edp", backend="pallas")
    assert [b.tag for b in got] == [b.tag for b in ref]
    assert [b.index for b in got] == [b.index for b in ref]


def test_per_arch_best_backend_param():
    jobs = _jobs(bypass_second=False)
    ref = per_arch_best(jobs, "edp", backend="jnp")
    got = per_arch_best(jobs, "edp", backend="pallas")
    assert [b.index for b in got] == [b.index for b in ref]


@pytest.mark.parametrize("entry", ["find_optimal_mapping", "per_arch_best"])
def test_jnp_scorer_error_propagates(monkeypatch, entry):
    """A batch-scorer failure (a device compile or memory error on a chip)
    must surface, never be re-scored on the host by the scalar loop."""
    import repro.core.batch_eval as be
    from repro.core import find_optimal_mapping

    def boom(*a, **k):
        raise RuntimeError("scorer failed on the device")

    monkeypatch.setattr(be, "batch_best_index", boom)
    with pytest.raises(RuntimeError, match="scorer failed on the device"):
        if entry == "find_optimal_mapping":
            find_optimal_mapping(TW.intra[2], _arch(), MapperConfig(
                max_mappings=300, seed=2), backend="jnp")
        else:
            per_arch_best(_jobs(bypass_second=True), "edp", backend="jnp")


@pytest.mark.parametrize("batching", ["fused", "per-arch"])
def test_run_search_same_best_under_either_backend(batching):
    space = ArchSpace.spatial(num_pes=(16, 64), rf_words=(64,),
                              gbuf_words=(2048, 8192), bits=16)
    reps = {}
    for be in ("jnp", "pallas"):
        reps[be] = run_search(TASK, space, goal="edp", cfg=NB_CFG,
                              strategy="exhaustive", batching=batching,
                              backend=be)
    a, b = reps["jnp"], reps["pallas"]
    assert a.best.hardware.name == b.best.hardware.name
    assert a.best_coords == b.best_coords
    # identical winning mappings, not just the same architecture
    for ra, rb in zip(a.best.per_workload, b.best.per_workload):
        assert ra.mapping.factors == rb.mapping.factors
        assert ra.mapping.orders == rb.mapping.orders
    assert a.goal_value() == pytest.approx(b.goal_value(), rel=1e-6)
    assert a.backend == "jnp" and b.backend == "pallas"
    assert b.summary()["backend"] == "pallas"


# ---------------------------------------------------------------------------
# cache: backend participates in the key; jnp/pallas never alias
# ---------------------------------------------------------------------------
def test_cache_key_backend_never_aliases():
    wl = TW.intra[2]
    hw = _arch()
    cfg = MapperConfig(max_mappings=100)
    ks = {cache_key(wl, hw, cfg, "edp", scorer=s, backend=b)
          for s in ("per-arch", "fused") for b in ("jnp", "pallas")}
    assert len(ks) == 4                  # all distinct
    assert cache_key(wl, hw, cfg, "edp", backend="jnp") == \
        cache_key(wl, hw, cfg, "edp", backend="jnp")


def test_shared_cache_isolates_backends():
    space = ArchSpace.spatial(num_pes=(16,), rf_words=(64,),
                              gbuf_words=(2048,), bits=16)
    cache = ResultCache()
    r1 = run_search(TASK, space, goal="edp", cfg=NB_CFG, cache=cache,
                    backend="jnp")
    assert r1.n_cache_misses > 0
    # same backend -> served from cache, zero enumerations
    r2 = run_search(TASK, space, goal="edp", cfg=NB_CFG, cache=cache,
                    backend="jnp")
    assert r2.n_enumerations == 0 and r2.n_cache_hits > 0
    # different backend -> no aliasing: every workload re-enumerated
    r3 = run_search(TASK, space, goal="edp", cfg=NB_CFG, cache=cache,
                    backend="pallas")
    assert r3.n_cache_hits == 0 and r3.n_enumerations > 0


# ---------------------------------------------------------------------------
# disk-tier GC bounds
# ---------------------------------------------------------------------------
def _fill(cache, n, pad=0):
    for i in range(n):
        cache.put(f"k{i:04d}", {"v": 2, "i": i, "pad": "x" * pad})
        # deterministic, strictly increasing mtimes (sub-second writes)
        os.utime(os.path.join(cache.path, f"k{i:04d}.json"), (i + 1, i + 1))


def _disk_keys(path):
    return sorted(f[:-5] for f in os.listdir(path) if f.endswith(".json"))


def test_disk_gc_entry_bound_evicts_oldest(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=8,
                    max_disk_bytes=None, gc_every=10_000)
    _fill(c, 20)
    assert c.gc() == 12
    assert _disk_keys(c.path) == [f"k{i:04d}" for i in range(12, 20)]
    assert c.stats.disk_evictions == 12
    assert c.gc() == 0                   # idempotent at the bound


def test_disk_gc_byte_bound(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=None,
                    max_disk_bytes=2048, gc_every=10_000)
    _fill(c, 12, pad=400)
    c.gc()
    total = sum(os.path.getsize(os.path.join(c.path, f))
                for f in os.listdir(c.path) if f.endswith(".json"))
    assert 0 < total <= 2048
    # survivors are the newest entries
    assert _disk_keys(c.path)[-1] == "k0011"


def test_disk_gc_triggers_on_put_cadence(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=4,
                    max_disk_bytes=None, gc_every=5)
    for i in range(20):
        c.put(f"k{i:04d}", {"v": 2, "i": i})
    # the put-path GC keeps the tier near the bound without explicit gc()
    assert len(_disk_keys(c.path)) <= 4 + 5
    assert c.stats.disk_evictions > 0


def test_disk_gc_sweeps_stale_tmp_and_seeds_estimates(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=50,
                    max_disk_bytes=None, gc_every=10)
    orphan = tmp_path / "orphan123.tmp"     # killed writer's sidecar
    orphan.write_text("x")
    os.utime(orphan, (1, 1))                # ancient -> stale
    for i in range(10):
        c.put(f"k{i:04d}", {"v": 2, "i": i})
    # cadence hit at put 10: the seeding scan runs, sweeps the orphan,
    # and (being under the bound) evicts nothing
    assert not orphan.exists()
    assert c.stats.disk_evictions == 0
    assert len(_disk_keys(c.path)) == 10


def test_disk_gc_unbounded_is_noop(tmp_path):
    c = ResultCache(path=str(tmp_path), max_disk_entries=None,
                    max_disk_bytes=None, gc_every=1)
    _fill(c, 10)
    assert c.gc() == 0
    assert len(_disk_keys(c.path)) == 10
