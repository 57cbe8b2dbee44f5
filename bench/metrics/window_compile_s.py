from bench.lib.engine_spans import window_compile_s as read  # noqa: F401
