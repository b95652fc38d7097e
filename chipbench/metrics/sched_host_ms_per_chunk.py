"""The paper's host overhead per chunk, (Tc2 - Tc1) + (Tc3 - Tg5), from
the scheduler's ``sched.chunk_host_s`` histograms: their sum over their
count, differenced between the snapshots before and after the window."""


def _totals(snap):
    n = s = 0.0
    for key, h in snap.get("histograms", {}).items():
        if key.startswith("sched.chunk_host_s"):
            n += h["count"]
            s += h["sum"]
    return n, s


def read(run):
    n0, s0 = _totals(run.tel_start)
    n1, s1 = _totals(run.tel_end)
    if n1 <= n0:
        return None
    return 1e3 * (s1 - s0) / (n1 - n0)
