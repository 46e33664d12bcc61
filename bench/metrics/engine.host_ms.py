"""The engine's own host time per epoch, over the window's epochs that the
profiler did not cover: the program's ``engine.epoch`` span less the spans
below it that are off the host's code (``spanclock.off_host``: the waits
on the device or the runtime, category ``wait``, and the service's
overlapped ingest), none counted inside another."""
from starbench import spanclock


def read(ctx):
    spans = ctx["spans"]
    want = {e["engine_epoch"] for e in ctx["epochs"]}
    if not want or not spanclock.has_tree(spans):
        return None
    roots = [s for s in spans if s["name"] == spanclock.ROOT
             and s["args"].get("epoch") in want]
    if not roots:
        return None
    out = 0.0
    for r in roots:
        out += r["dur_s"] - sum(
            s["dur_s"] for s in spanclock.descendants(
                spans, r, stop=spanclock.off_host)
            if spanclock.off_host(s))
    return out / len(roots) * 1e3
