"""Max over mean of the items each federated runtime completed in the
window (``FederationReport.per_runtime``, summed over the waves)."""


def read(run):
    if not run.per_runtime_items:
        return None
    totals = {}
    for wave in run.per_runtime_items:
        for rid, items in wave.items():
            totals[rid] = totals.get(rid, 0.0) + items
    mean = sum(totals.values()) / len(totals)
    return max(totals.values()) / mean if mean > 0 else None
