"""The serving benchmark's harness: everything a cell needs that is not
data. Configurations, their architectures' modules, traffic mixes, cells and
per-layer metric readers are files under bench/ found by name (see
bench/lib/spec.py); nothing here names an architecture."""
