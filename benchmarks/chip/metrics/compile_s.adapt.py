"""Seconds of backend compiles, persistent-cache loads included, during
set-up (the program's ``compile_s`` counters of the set-up session)."""


def read(run):
    stages = [e["value"] for e in run.get("setup_spans", ())
              if e.get("type") == "counter" and e["name"] == "compile_s"
              and e.get("attrs", {}).get("stage") == "backend_compile"]
    return sum(stages) if stages else None
