"""Per SpMV answered, CG's host vector work (``solver.vector`` spans: the
float64 cast of ``A p``, the dots and the updates), in milliseconds."""

from chipbench import spans as program


def value(spans, drops, spmvs):
    return program.ms_per_spmv(spans, drops, spmvs, "solver.vector")


def read(ctx):
    return value(*program.window(), ctx.spmvs)
