from bench.lib.readers import prefix_hit_rate as read  # noqa: F401
