"""matmul_int8 in the executed plan: least time over its device time."""


def read(rec):
    from bench.shares import kernel_roofline
    return kernel_roofline(rec, "matmul_int8")
