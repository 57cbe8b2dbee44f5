from bench.lib.engine_spans import step_device_wait_ms as read  # noqa: F401
