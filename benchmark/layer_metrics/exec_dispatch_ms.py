"""Executor host path: per step, the obs span `exec.step` minus its
`exec.writeback` child (which holds the wait for the device when the loss
comes back as numpy); median over the window's steps, in ms."""
import statistics


def read(record):
    spans = record.get("obs_spans") or []
    wait = {}
    for s in spans:
        if s["name"] == "exec.writeback":
            wait[s["parent"]] = wait.get(s["parent"], 0.0) + s["t1"] - s["t0"]
    host = [(s["t1"] - s["t0"] - wait.get(s["id"], 0.0)) * 1e3
            for s in spans if s["name"] == "exec.step"]
    return statistics.median(host) if host else None
