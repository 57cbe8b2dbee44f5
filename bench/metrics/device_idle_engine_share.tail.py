from bench.lib.engine_spans import device_idle_engine_share as read  # noqa: F401
