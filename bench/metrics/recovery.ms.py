"""Host time a node kill inside the window costs: the doomed epoch (the
``engine.epoch`` span marked ``doomed``) plus the ``recovery`` span of the
same epoch (classify, revert, restore, re-master and the re-execution).
The kill the run makes in set-up, before the window, is left out by its
epoch.  None where the program marks no doomed epoch."""


def read(ctx):
    spans = ctx["spans"]
    want = {e["engine_epoch"] for e in ctx["epochs"] + ctx["traced"]}
    doomed = {s["args"].get("epoch"): s for s in spans
              if s["name"] == "engine.epoch" and s["args"].get("doomed")}
    kills = [s for s in spans if s["name"] == "recovery"
             and s["args"].get("epoch") in want
             and s["args"].get("epoch") in doomed]
    if not kills:
        return None
    return sum(doomed[s["args"]["epoch"]]["dur_s"] + s["dur_s"]
               for s in kills) / len(kills) * 1e3
