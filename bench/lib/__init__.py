"""The serving benchmark's harness: everything a cell needs that is not
data. Configurations, traffic mixes, cells and per-layer metric readers are
files under bench/ found by name (see bench/lib/spec.py)."""
