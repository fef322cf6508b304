"""Per SpMV answered, the solver's per-solve set-up (``solver.setup`` spans:
the nonzero count, ``serve_optimize`` with its fingerprint, the float32
recompile's memo lookup), in milliseconds."""

from chipbench import spans as program


def value(spans, drops, spmvs):
    return program.ms_per_spmv(spans, drops, spmvs, "solver.setup")


def read(ctx):
    return value(*program.window(), ctx.spmvs)
