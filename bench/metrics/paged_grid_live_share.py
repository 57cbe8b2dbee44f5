from bench.lib.engine_spans import paged_grid_live_share as read  # noqa: F401
