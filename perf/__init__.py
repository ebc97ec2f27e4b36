"""tpu-search's benchmark: see perf/README.md."""
