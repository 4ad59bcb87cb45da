"""Paper case-study II as a runnable example: explore training-ASIC
designs (PEs x RF x Gbuf) for AlexNet-CIFAR with the lowest-EDP goal, then
show the effect of zero-skipping (case study I) on the winner.

    PYTHONPATH=src python examples/explore_training_asic.py
"""
import sys

sys.path.insert(0, "src")

import dataclasses

from repro.core import (MapperConfig, alexnet_cifar, evaluate_architecture,
                        analyze, explore, generate_arch_space,
                        make_spatial_arch)


def main():
    task = alexnet_cifar(batch_size=64)
    space = list(generate_arch_space(
        num_pes=(256, 512), rf_words=(128, 256),
        gbuf_words=(64 * 1024, 256 * 1024), bits=32, zero_skip=True))
    cfg = MapperConfig(max_mappings=1200, seed=0)
    res = explore(task, space, goal="edp", cfg=cfg, verbose=True)
    best = res.best.hardware
    print(f"\nlowest-EDP design: {best.name} "
          f"(EDP {res.best.network.edp:.3e}, "
          f"area {res.best.network.area_mm2:.1f} mm^2)")

    # zero-skipping ablation on the winning design (case study I)
    tw = analyze(task)
    on = evaluate_architecture(tw, best, cfg, goal="energy")
    off_hw = dataclasses.replace(best, zero_skip_level=None)
    off = evaluate_architecture(tw, off_hw, cfg, goal="energy")
    gain = off.network.energy_per_mac_pj / on.network.energy_per_mac_pj
    print(f"zero-skipping energy gain on winner: {gain:.2f}x "
          f"({off.network.energy_per_mac_pj:.2f} -> "
          f"{on.network.energy_per_mac_pj:.2f} pJ/MAC)")
    assert gain > 1.0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
