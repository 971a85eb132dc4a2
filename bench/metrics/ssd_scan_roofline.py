"""ssd_scan in the prefill step: least time over its device time. Its
events carry the name of the jitted wrapper of its Pallas call."""


def read(rec):
    from bench.shares import kernel_roofline
    return kernel_roofline(rec, "ssd_intra_chunk_bh")
