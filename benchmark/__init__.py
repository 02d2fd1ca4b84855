"""The benchmark of paddle-tpu on the TPU v5e: see BENCHMARK.json and PERF.md.

Everything that measures lives here, where a PR that claims a gain cannot
change it; the program is imported only as the system under test."""
