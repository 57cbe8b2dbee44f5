from bench.lib.readers import step_mfu as read  # noqa: F401
