"""The reader of the program's ``exec.cache_bytes_in_flight`` histograms,
on synthetic runs."""
import pytest

from chipbench import harness

CACHE = harness.load_reader("cache_gb_in_flight")


def _run(hists):
    run = harness.Run(chips=1, peaks=None)
    run.tel_end = {"histograms": {
        f'exec.cache_bytes_in_flight{{group="{g}"}}': {"count": n, "max": mx}
        for g, (n, mx) in hists.items()}}
    run.tel_end["histograms"]['exec.issue_cpu_s{group="accel"}'] = {
        "count": 9, "max": 5e9}
    return run


def test_largest_observation_of_any_group_in_gb():
    run = _run({"accel": (12, 1.49143552e9), "cpu0": (12, 0.74571776e9)})
    assert CACHE(run) == pytest.approx(1.49143552)


@pytest.mark.parametrize("hists", [{}, {"accel": (0, 0.0)}])
def test_none_without_an_observation(hists):
    assert CACHE(_run(hists)) is None
