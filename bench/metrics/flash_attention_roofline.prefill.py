"""flash_attention in the prefill step: least time over its device time."""


def read(rec):
    from bench.shares import kernel_roofline
    return kernel_roofline(rec, "flash_attention")
