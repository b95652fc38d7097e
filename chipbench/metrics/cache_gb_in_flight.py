"""The largest observation of the program's ``exec.cache_bytes_in_flight``
histograms (one per executor group) in the end snapshot: the most decode
cache, in GB (1e9 bytes), that one group's chunks held at once between
their prefill and the fetch of their outputs, set-up's warm-up included.
None where the program has no such histogram."""


def read(run):
    peaks = [h["max"] for key, h in run.tel_end.get("histograms", {}).items()
             if key.startswith("exec.cache_bytes_in_flight") and h["count"]]
    return max(peaks) / 1e9 if peaks else None
