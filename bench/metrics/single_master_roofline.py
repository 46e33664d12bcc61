"""Share of the single-master program's device time that the least traffic
of its work would take at the chip's peak HBM bandwidth: bytes the traced
batches' live cross-partition transactions need (``starbench.work``),
each counted once however many OCC rounds it took, over the bandwidth,
divided by the program's device time."""
from starbench import devtrace, work

PROGRAMS = {"jit_run_single_master"}


def read(ctx):
    t = devtrace.module_seconds(ctx["device_events"], PROGRAMS)
    if not t:
        return None
    need = sum(work.single_master_bytes(e["batch"], ctx["n_cols"])
               for e in ctx["traced"])
    return need / ctx["peaks"]["hbm_bytes_per_s"] / t * 100
