"""Edges still queued (events) when the window closed: near 0 where the
offered rate is sustained."""


def read(run):
    return run.get("counters", {}).get("backlog_end")
