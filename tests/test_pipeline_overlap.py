"""Streaming DSE pipeline tests: bit-identical results vs the sequential
loop, lookahead degradation rules, multi-device shard planning, async
cache writeback, and jit-compile visibility."""
import jax
import numpy as np
import pytest

from repro.core import (Conv2D, FC, MapperConfig, Pool2D, TaskDescription,
                        Workload, build_mapspace, generate_arch_space,
                        make_spatial_arch)
from repro.core.batch_eval import SHARD_MIN_ROWS, shard_bounds
from repro.search import (MapspaceJob, ResultCache, fused_best, run_search)
from repro.search.cache import CACHE_FORMAT
from repro.search import batch_frontier as bf
from repro.search.batch_frontier import fused_collect, fused_launch
from repro.search.driver import (AUTO_ROUND_MAX, AUTO_ROUND_MIN,
                                 TARGET_FUSED_ROWS, auto_round_size)
from repro.search.space import as_space
from repro.search.strategies import ExhaustiveStrategy

TASK = TaskDescription(
    name="tiny", input_shape=(8, 8, 3), batch_size=2,
    processing_type="Inference",
    layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
            Pool2D((2, 2), (2, 2), name="p1"),
            FC(10, name="fc")))
CFG = MapperConfig(max_mappings=200, seed=0)


def arch_list():
    return list(generate_arch_space(num_pes=(16, 64), rf_words=(64,),
                                    gbuf_words=(2048, 8192), bits=16))


def _fingerprint(rep):
    """Everything the streaming rewrite promises to preserve exactly."""
    return {
        "best_coords": rep.best_coords,
        "goal_value": rep.goal_value(),
        "history": rep.history,
        "order": [r.hardware.name for r in rep.all_archs],
        "frontier": sorted((p.key, p.values) for p in rep.pareto.points()),
        "n_evaluated": rep.n_evaluated,
    }


# ---------------------------------------------------------------------------
# bit-identical winners: streaming vs sequential
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["exhaustive", "random"])
@pytest.mark.parametrize("seed", [0, 7])
def test_streaming_bit_identical(strategy, seed):
    kw = dict(goal="edp", cfg=CFG, strategy=strategy, seed=seed,
              round_size=1)
    seq = run_search(TASK, arch_list(), overlap=False, **kw)
    stream = run_search(TASK, arch_list(), overlap=True, **kw)
    assert not seq.overlap
    assert stream.overlap
    assert _fingerprint(stream) == _fingerprint(seq)


def test_streaming_default_auto_engages_for_lookahead():
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, round_size=2)
    assert rep.overlap          # overlap="auto" + exhaustive + fused


def test_adaptive_strategy_degrades_to_sync():
    # anneal's ask depends on tell feedback: overlap=True must not force
    # a lookahead pipeline on it, only fall back to the sequential loop
    for overlap in ("auto", True):
        rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                         strategy="anneal", budget=4, overlap=overlap)
        assert not rep.overlap
    base = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                      strategy="anneal", budget=4, overlap=False)
    got = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                     strategy="anneal", budget=4, overlap=True)
    assert _fingerprint(got) == _fingerprint(base)


def test_per_arch_batching_degrades_to_sync():
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                     batching="per-arch", overlap=True)
    assert not rep.overlap


def test_overlap_rejects_bad_value():
    with pytest.raises(ValueError, match="overlap"):
        run_search(TASK, arch_list(), goal="edp", cfg=CFG, overlap="yes")


# ---------------------------------------------------------------------------
# warm-cache streaming replay + async writeback
# ---------------------------------------------------------------------------
def test_warm_cache_streaming_replay(tmp_path):
    cache_dir = str(tmp_path / "c")
    cold = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                      overlap=True, round_size=1, cache=cache_dir)
    assert cold.overlap and cold.n_enumerations > 0
    warm = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                      overlap=True, round_size=1, cache=cache_dir)
    assert warm.overlap
    assert warm.n_enumerations == 0     # async puts landed on disk
    assert warm.n_cache_misses == 0
    assert _fingerprint(warm) == _fingerprint(cold)


def test_async_writer_flushes_on_midrun_exception(tmp_path):
    cache_dir = str(tmp_path / "c")

    class Boom(ExhaustiveStrategy):
        name = "boom"
        tells = 0

        def tell(self, batch):
            Boom.tells += 1
            if Boom.tells >= 2:
                raise RuntimeError("mid-run failure")

    strat = Boom(as_space(arch_list()), seed=0)
    with pytest.raises(RuntimeError, match="mid-run failure"):
        run_search(TASK, arch_list(), goal="edp", cfg=CFG, strategy=strat,
                   overlap=True, round_size=1, cache=cache_dir)
    # puts completed before the failure were drained to disk, not lost
    # in the writer queue
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG,
                     overlap=False, cache=cache_dir)
    assert rep.n_cache_hits > 0


def test_cache_level_async_writer_roundtrip(tmp_path):
    cache = ResultCache(path=str(tmp_path / "c"))
    assert cache.stop_async_writes() == 0       # idempotent with no writer
    cache.start_async_writes()
    e1 = {"v": CACHE_FORMAT, "payload": 1}
    e2 = {"v": CACHE_FORMAT, "payload": 2}
    cache.put("k1", e1)
    cache.put("k2", e2)
    assert cache.stop_async_writes() == 2
    assert cache.writer_errors == []
    fresh = ResultCache(path=str(tmp_path / "c"))
    assert fresh.get("k1") == e1
    assert fresh.get("k2") == e2


# ---------------------------------------------------------------------------
# shard planning
# ---------------------------------------------------------------------------
def test_shard_bounds_units():
    assert shard_bounds(0, 3) == [(0, 0)]
    assert shard_bounds(100, 4) == [(0, 100)]           # min_rows guard
    assert shard_bounds(2 * SHARD_MIN_ROWS, 2) == \
        [(0, SHARD_MIN_ROWS), (SHARD_MIN_ROWS, 2 * SHARD_MIN_ROWS)]
    # near-equal split, remainder to the front, contiguous cover
    b = shard_bounds(10001, 2, min_rows=1)
    assert b == [(0, 5001), (5001, 10001)]
    b = shard_bounds(100, 7, min_rows=10)
    assert b[0][0] == 0 and b[-1][1] == 100
    assert all(hi == nxt_lo for (_, hi), (nxt_lo, _) in zip(b, b[1:]))
    assert all(hi - lo >= 10 for lo, hi in b)
    # k clamps to what min_rows allows
    assert len(shard_bounds(9000, 4)) == 2


def test_shard_plan_single_device_is_unsharded():
    assert bf._shard_plan(10 ** 6, devices=["d0"]) == [((0, 10 ** 6),
                                                        None)]
    assert bf._shard_plan(100, devices=["d0", "d1"]) == [((0, 100), None)]


def test_shard_plan_multi_device_assignment():
    n = 4 * SHARD_MIN_ROWS
    plan = bf._shard_plan(n, devices=["d0", "d1"])
    assert [b for b, _ in plan] == [(0, n // 2), (n // 2, n)]
    assert [d for _, d in plan] == ["d0", "d1"]


def test_kernel_shard_plan_units():
    # single device / small totals: jobs stay whole, no pinning
    assert bf._kernel_shard_plan([0, 1], [10, 10], devices=["d0"]) == \
        [([0, 1], None)]
    assert bf._kernel_shard_plan([0, 1], [10, 10],
                                 devices=["d0", "d1"]) == [([0, 1], None)]
    # big enough: jobs partitioned by row weight, whole jobs only
    cnt = SHARD_MIN_ROWS
    plan = bf._kernel_shard_plan([0, 1, 2, 3], [cnt] * 4,
                                 devices=["d0", "d1"])
    assert [idxs for idxs, _ in plan] == [[0, 1], [2, 3]]
    assert [d for _, d in plan] == ["d0", "d1"]
    # every job appears exactly once even with skewed weights
    plan = bf._kernel_shard_plan([0, 1, 2], [3 * cnt, cnt, cnt],
                                 devices=["d0", "d1"])
    assert sorted(i for idxs, _ in plan for i in idxs) == [0, 1, 2]


def _fused_jobs():
    wl = Workload(dims=(2, 8, 4, 3, 3, 4, 4), input_zero_frac=0.2)
    hws = [make_spatial_arch(num_pes=16, rf_words=64, gbuf_words=4096,
                             bits=16, zero_skip=True),
           make_spatial_arch(num_pes=64, rf_words=128, gbuf_words=16384,
                             bits=16, zero_skip=False)]
    return [MapspaceJob(tag=i, hw=hw, workload=wl,
                        mappings=build_mapspace(wl, hw, CFG).mappings)
            for i, hw in enumerate(hws)]


def test_forced_two_shard_equality(monkeypatch):
    jobs = _fused_jobs()
    base = fused_best(jobs, "edp")

    def split_plan(n, devices=None):
        if n < 2:
            return [((0, n), None)]
        return [((0, n // 2), None), ((n // 2, n), None)]

    monkeypatch.setattr(bf, "_shard_plan", split_plan)
    sharded = fused_best(jobs, "edp")
    # row-wise evaluator: per-shard pad + host merge is bit-identical
    assert [(b.tag, b.index, b.value, b.n_scored) for b in sharded] == \
        [(b.tag, b.index, b.value, b.n_scored) for b in base]


def test_fused_launch_collect_matches_fused_best():
    jobs = _fused_jobs()
    base = fused_best(jobs, "edp")
    got = fused_collect(fused_launch(jobs, "edp"))
    assert [(b.tag, b.index, b.value, b.n_scored) for b in got] == \
        [(b.tag, b.index, b.value, b.n_scored) for b in base]


# ---------------------------------------------------------------------------
# auto round sizing scales with device count
# ---------------------------------------------------------------------------
def test_auto_round_size_single_device_is_historical():
    assert auto_round_size(1000.0, n_devices=1) == \
        max(AUTO_ROUND_MIN, min(AUTO_ROUND_MAX,
                                TARGET_FUSED_ROWS // 1000))
    assert auto_round_size(10 ** 9, n_devices=1) == AUTO_ROUND_MIN
    assert auto_round_size(1.0, n_devices=1) == AUTO_ROUND_MAX


def test_auto_round_size_scales_with_devices():
    one = auto_round_size(4096.0, n_devices=1)
    four = auto_round_size(4096.0, n_devices=4)
    assert four == 4 * one          # both caps scale linearly
    assert auto_round_size(1.0, n_devices=4) == 4 * AUTO_ROUND_MAX
    # the floor does not scale: huge mapspaces still get minimal rounds
    assert auto_round_size(10 ** 9, n_devices=4) == AUTO_ROUND_MIN


# ---------------------------------------------------------------------------
# observability: new phases + jit-compile visibility
# ---------------------------------------------------------------------------
def test_streaming_trace_has_pipeline_phases(tmp_path):
    jax.clear_caches()                  # cold start: every program compiles
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=True,
                     overlap=True, round_size=1,
                     cache=str(tmp_path / "c"))
    assert rep.overlap
    spans = rep.tracer.buffer.snapshot()
    names = {s.name for s in spans}
    assert {"prefetch-build", "device-wait", "cache-flush"} <= names
    assert {"prefetch-build", "device-wait", "cache-flush"} <= \
        set(rep.phase_times)
    # the deferred launch is still attributed to the score phase
    score = [s for s in spans if s.name == "score"]
    assert score and all(s.attrs.get("deferred") for s in score)

    jit = rep.summary()["jit"]
    assert jit["dispatches"] >= 1
    # JAX's own events: the fused scorer was traced, lowered and compiled
    fused = jit["by_fun"]["evaluate_batch_multi"]
    assert fused["trace"]["n"] >= 1 and fused["lower"]["n"] >= 1
    assert fused["compile"]["n"] >= 1
    assert jit["compile"]["n"] >= fused["compile"]["n"]
    assert jit["compile"]["s"] > 0
    hist = jit["bucket_rows"]
    assert hist["count"] == jit["dispatches"]
    # bucket padding: every dispatched row count is a power of two
    assert float(hist["max"]) == 2 ** int(np.log2(hist["max"]))


def test_jit_registry_dedups_recompiles():
    jax.clear_caches()
    r1 = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=True,
                    round_size=1)
    r2 = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=True,
                    round_size=1)
    j1, j2 = r1.summary()["jit"], r2.summary()["jit"]
    assert j1["by_fun"]["evaluate_batch_multi"]["compile"]["n"] >= 1
    # the second run reuses every executable jit's cache holds: nothing
    # is traced, lowered or compiled again
    assert "evaluate_batch_multi" not in j2["by_fun"]
    assert j2["compile"]["n"] == 0 and j2["trace"]["n"] == 0
    assert j2["dispatches"] >= 1


def test_streaming_main_thread_phases_fit_the_wall_time():
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG, trace=True,
                     overlap=True, round_size=1)
    assert rep.overlap
    by = rep.phase_times_by_thread
    assert {"main", "prefetch"} <= set(by)
    assert "pack" in by["prefetch"] and "prefetch-build" in by["main"]
    # the main thread's phases never overlap: their sum fits the wall
    assert sum(by["main"].values()) <= rep.wall_time_s
    # phase_times stays the sum over threads
    for name, v in rep.phase_times.items():
        assert v == pytest.approx(sum(d.get(name, 0.0)
                                      for d in by.values()))
    assert rep.summary()["phase_times_by_thread"] == by


def test_summary_jit_absent_without_trace():
    rep = run_search(TASK, arch_list(), goal="edp", cfg=CFG)
    assert rep.summary()["jit"] is None
