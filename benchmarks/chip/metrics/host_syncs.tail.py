"""Blocking device-to-host reads per superstep of the window (the
program's ``host_syncs`` counter, one sample per ``step()``)."""


def read(run):
    counts = [e["value"] for e in run.get("spans", ())
              if e.get("type") == "counter" and e["name"] == "host_syncs"]
    return sum(counts) / len(counts) if counts else None
