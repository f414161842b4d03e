"""The benchmark: harness, traffic, reference, checks and trace reduction."""
