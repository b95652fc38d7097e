"""Share of the batch rows the executor ran that held no request: the
program's ``exec.rows{kind="padded"}`` counters over ``kind="real"`` plus
``kind="padded"`` (each chunk padded to its batch bucket), differenced
between the snapshots before and after the window, in %. None where the
program has no such counter."""


def _rows(snap):
    real = padded = 0.0
    for key, value in snap.get("counters", {}).items():
        if key.startswith("exec.rows{"):
            if 'kind="padded"' in key:
                padded += value
            elif 'kind="real"' in key:
                real += value
    return real, padded


def read(run):
    r0, p0 = _rows(run.tel_start)
    r1, p1 = _rows(run.tel_end)
    total = (r1 - r0) + (p1 - p0)
    if total <= 0:
        return None
    return 100.0 * (p1 - p0) / total
