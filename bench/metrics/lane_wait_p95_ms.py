from bench.lib.engine_spans import lane_wait_p95_ms as read  # noqa: F401
