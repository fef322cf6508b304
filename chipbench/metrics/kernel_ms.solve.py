"""Device time per SpMV of the Mosaic kernels, in milliseconds."""


def read(ctx):
    r = ctx.reduction
    if r.spmv_calls == 0 or r.kernel_ns == 0:
        return None
    return r.kernel_ns / r.spmv_calls / 1e6
