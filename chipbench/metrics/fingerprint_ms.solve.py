"""Per SpMV answered, the session's hashing of the dense input
(``session.fingerprint`` spans), in milliseconds."""

from chipbench import spans as program


def value(spans, drops, spmvs):
    return program.ms_per_spmv(spans, drops, spmvs, "session.fingerprint")


def read(ctx):
    return value(*program.window(), ctx.spmvs)
