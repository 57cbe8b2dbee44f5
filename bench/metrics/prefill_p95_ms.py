from bench.lib.engine_spans import prefill_p95_ms as read  # noqa: F401
