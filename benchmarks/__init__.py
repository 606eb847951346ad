"""The benchmark: harness, yardstick and plain references. See README.md."""
