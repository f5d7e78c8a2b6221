"""Benchmark harness: drivers, traffic, trace reduction and the yardstick."""
