"""The benchmark: harness, load generators, trace reduction, references (see PERF.md)."""
