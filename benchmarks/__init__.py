"""The benchmark: harness, jobs, data files, references and metric code (see PERF.md)."""
