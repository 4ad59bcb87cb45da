"""Frozen copy of TRIM's scalar cost model: Designer templates
(`designer`), workloads (`workload`, `task_analyst`), mappings
(`mapping`), the mapping constructor/validator (`mapper`) and the float64
evaluator (`evaluator`).  It imports nothing of the program under test,
so no change to the program can move it.  The only departures from the
program's modules: the mapper emits no spans, and the evaluator takes the
number type its floats are born in (`F`, see `bench.reference.lowp`)."""
