from bench.lib.engine_spans import row_fill_share as read  # noqa: F401
