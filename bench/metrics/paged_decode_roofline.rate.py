from bench.lib.readers import paged_decode_roofline as read  # noqa: F401
