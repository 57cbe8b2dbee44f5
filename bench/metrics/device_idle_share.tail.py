from bench.lib.readers import device_idle_share as read  # noqa: F401
