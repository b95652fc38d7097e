"""Median of finished_at - created_at over every job of the window; a
job is one client request of one sequence."""
from chipbench.harness import percentile


def read(run):
    lat = [j.finished - j.created for j in run.jobs if j.finished is not None]
    return 1e3 * percentile(lat, 50) if lat else None
