"""How long the federated runtimes sat with no epoch in flight during
the window's serve calls, in %: for each of the program's ``serve``
spans (one per ``serve_jobs_federated`` call, its runtimes in
``args.runtimes``), each runtime's drained time is the span less the
union of that runtime's ``epoch:`` spans (track ``<runtime>/epochs``)
inside it; the sum over calls and runtimes, over runtimes times span.
Read only where the span tracer dropped nothing; None where the program
records no ``serve`` span."""


def _union_s(intervals):
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def read(run):
    if run.trace_dropped:
        return None
    names = {ev["tid"]: ev["args"]["name"] for ev in run.spans
             if ev.get("ph") == "M" and ev.get("name") == "thread_name"}
    epochs = {}
    serves = []
    lo, hi = (t * 1e6 for t in run.mono_window)
    for ev in run.spans:
        if ev.get("ph") != "X":
            continue
        a, b = ev["ts"], ev["ts"] + ev["dur"]
        if ev["name"] == "serve" and names.get(ev["tid"]) == "serve":
            if lo <= a and b <= hi and b > a:
                serves.append((a, b, ev["args"].get("runtimes", [])))
        elif ev["name"].startswith("epoch:"):
            epochs.setdefault(names.get(ev["tid"]), []).append((a, b))
    drained = whole = 0.0
    for a, b, runtimes in serves:
        for rid in runtimes:
            inside = [(max(x, a), min(y, b))
                      for x, y in epochs.get(f"{rid}/epochs", ())
                      if y > a and x < b]
            drained += (b - a) - _union_s(inside)
            whole += b - a
    return 100.0 * drained / whole if whole > 0 else None
