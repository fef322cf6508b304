"""Per SpMV answered, the CPU time the solving thread spent in the kernel
on the solve's behalf (page faults, zeroing, compaction): the ``sys_s``
counter of the ``solver.solve`` roots, in milliseconds."""

from chipbench import spans as program


def value(spans, drops, spmvs):
    roots = [s for s in program.named(spans, "solver.solve") if s["parent"] is None]
    sys_s = [(s.get("attrs") or {}).get("sys_s") for s in roots]
    if drops or not roots or None in sys_s or spmvs == 0:
        return None
    return sum(sys_s) * 1e3 / spmvs


def read(ctx):
    return value(*program.window(), ctx.spmvs)
