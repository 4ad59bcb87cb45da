"""Pure-jnp oracle for the mapspace-eval kernel: the one jnp cost model,
`core.batch_eval`, restricted to no-bypass mappings (the kernel's semantics
are defined as equal to this — and batch_eval itself is validated against
the scalar evaluator and the brute-force loop simulator)."""
from __future__ import annotations

from typing import Sequence

import numpy as np

from ...core.batch_eval import batch_scores_arrays, make_static, pack
from ...core.mapping import Mapping


def mapspace_eval_ref(mappings: Sequence[Mapping]):
    """-> (cycles [n], energy [n]) float64/float32 arrays."""
    st = make_static(mappings[0].hardware, mappings[0].workload)
    factors, rank, store = pack(mappings)
    cycles, _ = batch_scores_arrays(st, factors, rank, store, "latency")
    energy, _ = batch_scores_arrays(st, factors, rank, store, "energy")
    return np.asarray(cycles), np.asarray(energy)
