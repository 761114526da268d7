"""The benchmark: harness, yardstick and data. See benchmark/README.md."""
