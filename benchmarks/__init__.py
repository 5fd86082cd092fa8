"""The benchmark: one command, cells as data. See benchmarks/README.md."""
