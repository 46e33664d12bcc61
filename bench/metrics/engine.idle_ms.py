"""Device idle per traced epoch that the engine's own host code holds:
stretches inside the traced ``engine.run_epoch`` annotations in which the
first device ran nothing and whose innermost program span (placed on the
trace's clock by ``starbench.spanclock``) lies below ``engine.epoch``,
outside the waits on the device or the runtime (category ``wait``) and
the service's overlapped ingest."""
from starbench import spanclock


def read(ctx):
    pieces = spanclock.traced_idle(ctx)
    if pieces is None:
        return None
    by_id = {s["id"]: s for s in ctx["spans"]}
    ns = sum(n for span, n in pieces if span is not None
             and spanclock.engine_host_segment(span, by_id))
    return ns / len(ctx["traced"]) / 1e6
