"""Device time per SpMV of the SpMV program's other ops (today the XLA
gather of x), in milliseconds."""


def read(ctx):
    r = ctx.reduction
    if r.spmv_calls == 0 or r.xla_ns == 0:
        return None
    return r.xla_ns / r.spmv_calls / 1e6
