"""Host wall time inside the executor's step call per generated token:
the tracer's ``launch`` spans (Tg2 -> Tg3), summed over the window's
chunks and divided by the tokens generated. The step issues a chunk's
prefill and per-token decode calls, and the host blocks in it behind the
device once the runtime's queue is full, so this follows the device time
of those calls as well as the cost of issuing them. Read only where the
span tracer dropped nothing."""


def read(run):
    if run.trace_dropped or not run.generated_tokens:
        return None
    lo, hi = (t * 1e6 for t in run.mono_window)
    total_us = sum(ev["dur"] for ev in run.spans
                   if ev.get("name") == "launch" and lo <= ev["ts"] <= hi)
    return total_us / run.generated_tokens if total_us else None
