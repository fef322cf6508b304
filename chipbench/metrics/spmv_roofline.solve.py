"""Share of the HBM roofline one SpMV reaches, in percent: the least time
of its compulsory bytes (``chipbench.roofline``) over the device time of
the whole SpMV program (kernels and the other ops together)."""


def read(ctx):
    r = ctx.reduction
    device_ns = r.kernel_ns + r.xla_ns
    if r.spmv_calls == 0 or device_ns == 0:
        return None
    return 100.0 * ctx.least_s * 1e9 / (device_ns / r.spmv_calls)
