"""Host time per epoch that the service spends pulling arrivals, admitting
them and forming the next batch, overlapped with the partitioned phase:
the program's ``service.ingest_overlap`` span, summed per epoch."""


def read(ctx):
    want = {e["engine_epoch"] for e in ctx["epochs"]}
    spans = [s["dur_s"] for s in ctx["spans"]
             if s["name"] == "service.ingest_overlap"
             and s["args"].get("epoch") in want]
    if not want or not spans:
        return None
    return sum(spans) / len(want) * 1e3
