"""Storage (storage/tiered.py, ps/server.py): hot and warm hits over all
accesses in the window, from the parameter server's counters."""
UNIT = "%"


def read(run):
    s = run.ps_stats
    if not s.get("total_accesses"):
        return None
    return 100.0 * (s["hot_hits"] + s["warm_hits"]) / s["total_accesses"]
