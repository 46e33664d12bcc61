"""Host time per epoch that the service spends retiring the answered batch
after the engine call returns (stamping, recording latencies, re-queueing
starved transactions, waking closed-loop clients): the program's
``service.complete`` span, over the window's epochs that the profiler did
not cover."""


def read(ctx):
    want = {e["engine_epoch"] for e in ctx["epochs"]}
    spans = [s["dur_s"] for s in ctx["spans"]
             if s["name"] == "service.complete"
             and s["args"].get("epoch") in want]
    if not want or not spans:
        return None
    return sum(spans) / len(want) * 1e3
