"""Device idle (ms) inside the per-round history readback (the program's
``adapt.history`` spans on the profiler's clock) per profiled adaptation."""


def read(run):
    gaps = dict((run.get("trace") or {}).get("idle_gaps", ()))
    adaptations = sum(1 for e in run.get("spans", ())
                      if e.get("type") == "span" and e["name"] == "adapt"
                      and e["depth"] == 0)
    if not adaptations or not any(g.startswith("xdgp/") for g in gaps):
        return None
    return 1e3 * gaps.get("xdgp/adapt.history", 0.0) / adaptations
