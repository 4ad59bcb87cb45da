"""The benchmark's plain reference: a float64 scalar cost model of TRIM
(`trim/`, a frozen copy that imports nothing of the program) and the
comparison that decides a run's `correct` (`check.py`)."""
