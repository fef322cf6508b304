"""Per SpMV answered, the host work between SpMVs: the ``solver.iterate``
spans less the ``kernel.execute`` spans inside them (the solver's step on
the host, the session's bookkeeping), in milliseconds."""

from chipbench import spans as program


def value(spans, drops, spmvs):
    iterate = {s["id"]: s for s in program.named(spans, "solver.iterate")}
    execute = [s for s in program.named(spans, "kernel.execute") if s["parent"] in iterate]
    if drops or not execute or spmvs == 0:
        return None
    host_s = sum(s["dur_s"] for s in iterate.values()) - sum(s["dur_s"] for s in execute)
    return host_s * 1e3 / spmvs


def read(ctx):
    return value(*program.window(), ctx.spmvs)
