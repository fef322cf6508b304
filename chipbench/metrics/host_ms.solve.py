"""Per SpMV answered, the request time in which no op ran on the device
(the solver's and the session's host work), in milliseconds."""


def read(ctx):
    r = ctx.reduction
    if ctx.spmvs == 0 or r.request_ns == 0:
        return None
    return r.request_idle_ns / ctx.spmvs / 1e6
