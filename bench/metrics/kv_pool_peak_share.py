from bench.lib.readers import kv_pool_peak_share as read  # noqa: F401
