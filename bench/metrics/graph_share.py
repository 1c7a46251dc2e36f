"""Share of the device's busy time spent in the layer graph's non-conv ops
(named scopes ``repro.graph.bn``, ``.pool`` and ``.add``; trace_scopes.py)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["busy_s"] or "graph_s" not in rec:
        return None
    return 100.0 * rec["graph_s"] / t["busy_s"]
