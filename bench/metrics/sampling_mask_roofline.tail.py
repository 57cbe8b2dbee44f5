from bench.lib.readers import sampling_mask_roofline as read  # noqa: F401
