"""Quickstart: TRIM end-to-end in ~40 lines (paper Fig. 1 pipeline).

Builds the task description for AlexNet-CIFAR training, explores a small
architecture space, prints the optimal design point + its best mapping in
the paper's loop-nest format.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.core import (MapperConfig, alexnet_cifar, explore,
                        generate_arch_space)


def main():
    task = alexnet_cifar(batch_size=16)
    arch_space = list(generate_arch_space(
        num_pes=(64, 256), rf_words=(128, 256),
        gbuf_words=(32 * 1024, 128 * 1024), bits=32, zero_skip=True))
    cfg = MapperConfig(max_mappings=1500, seed=0, pe_utilization_min=0.5)

    print(f"exploring {len(arch_space)} architectures "
          f"x {len(cfg.orders)} mapspaces (goal: lowest EDP)\n")
    result = explore(task, arch_space, goal="edp", cfg=cfg, verbose=True)

    best = result.best
    n = best.network
    print(f"\noptimal architecture: {best.hardware.name}")
    print(f"  cycles       : {n.cycles:.4e}")
    print(f"  energy       : {n.energy_pj / 1e6:.3f} uJ")
    print(f"  EDP          : {n.edp:.4e}")
    print(f"  area         : {n.area_mm2:.2f} mm^2")
    print(f"  preprocessing: {n.preproc_cycles:.3e} cycles (inter-layer)")
    print(f"  activations  : {n.onchip_cached_words:.0f} words on-chip, "
          f"{n.dram_cached_words:.0f} spilled to DRAM")

    wr = best.per_workload[0]
    print(f"\nbest mapping for {wr.workload.name} "
          f"(dims N,M,C,R,S,E,F = {wr.workload.dims}):")
    print(wr.mapping.render())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
