"""Host time per epoch in the engine's two fences (``engine.fence``,
which=1 and 2: the commit-count barrier, the drain of the replicas'
streams, the readback and the commit), over the window's epochs that the
profiler did not cover.  The fences of a doomed epoch (an ``engine.epoch``
span marked ``doomed``, which re-executes under the same number) are left
out."""
from starbench import spanclock


def read(ctx):
    spans = ctx["spans"]
    want = {e["engine_epoch"] for e in ctx["epochs"]}
    if not want or not spanclock.has_tree(spans):
        return None
    lost = {s["id"] for root in spans
            if root["name"] == spanclock.ROOT and root["args"].get("doomed")
            for s in spanclock.descendants(spans, root)}
    fences = [s["dur_s"] for s in spans
              if s["name"] == "engine.fence" and s["id"] not in lost
              and s["args"].get("epoch") in want]
    if not fences:
        return None
    return sum(fences) / len(want) * 1e3
