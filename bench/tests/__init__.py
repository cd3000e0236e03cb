"""CPU tests of the benchmark harness, at smoke size."""
