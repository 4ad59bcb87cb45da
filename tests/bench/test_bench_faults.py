"""The comparison that decides `correct` fails a run whose timed path is
broken underneath: half of each mapspace left out of the scoring, a
winner altered where it is chosen, and a winner's estimate altered where
it is produced.  (A query keeps no state from one query to the next and
runs on one chip, so the other faults do not exist in these cells.)
The harness runs on the CPU with its chip check skipped."""
import dataclasses

import pytest

from bench_tiny import make_root, run_tiny


def _half_rows(build):
    def build_half(wl, hw, cfg):
        pm = build(wl, hw, cfg)
        h = max(1, len(pm) // 2)
        return dataclasses.replace(
            pm, factors=pm.factors[h:], rank=pm.rank[h:],
            store=pm.store[h:], fi=pm.fi[h:], oi=pm.oi[h:], bi=pm.bi[h:])
    return build_half


def _next_winner(collect):
    def collect_next(pending):
        bests = collect(pending)
        return [dataclasses.replace(b, index=(b.index + 1) % b.n_scored)
                for b in bests]
    return collect_next


def _scaled_energy(evaluate):
    def evaluate_scaled(m, *a, **k):
        est = evaluate(m, *a, **k)
        return dataclasses.replace(est, dynamic_pj=est.dynamic_pj * 1.001)
    return evaluate_scaled


FAULTS = [
    ("half the rows", "repro.search.driver.build_packed_mapspace",
     _half_rows, "regret"),
    ("winner altered", "repro.search.driver.fused_collect",
     _next_winner, "regret"),
    ("estimate altered", "repro.search.driver.evaluate_mapping",
     _scaled_energy, "estimate_gap"),
]


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)


def test_sound_run_is_correct(root, monkeypatch, capsys):
    rc, result, err = run_tiny(root, monkeypatch, capsys, seed=11)
    assert rc == 0, err
    assert result["correct"] is True


@pytest.mark.parametrize("fault,target,wrap,number", FAULTS)
def test_fault_is_not_correct(root, monkeypatch, capsys, fault, target,
                              wrap, number):
    import importlib
    mod_name, attr = target.rsplit(".", 1)
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    rc, result, err = run_tiny(root, monkeypatch, capsys, seed=11)
    assert rc == 0, err
    assert result["correct"] is False, fault
    c = result["compared"][number]
    assert c["value"] > c["limit"], (fault, c)
    assert "FAILED" in err
