from bench.lib.readers import queue_wait_p95_ms as read  # noqa: F401
