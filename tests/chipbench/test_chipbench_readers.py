"""The readers of the program's executor counters and federation spans,
on synthetic runs; and the program names the device-trace reduction
matches."""
import pytest

from chipbench import harness, trace

PADDED = harness.load_reader("padded_row_share")
CPU = harness.load_reader("dispatch_cpu_us_per_token")
DRAINED = harness.load_reader("fed_runtime_drained_share")


def _snap(rows=None, cpu=None, wait=None):
    counters = {f'exec.rows{{group="{g}",kind="{k}"}}': v
                for (g, k), v in (rows or {}).items()}
    counters["sched.chunks{group=\"accel\"}"] = 99.0
    hists = {f'exec.{name}{{group="{g}"}}': {"count": n, "sum": s}
             for name, per_group in (("issue_cpu_s", cpu),
                                     ("wait_cpu_s", wait))
             for g, (n, s) in (per_group or {}).items()}
    return {"counters": counters, "histograms": hists}


def _run(**kw):
    run = harness.Run(chips=1, peaks=None)
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def test_padded_row_share_differences_the_window():
    start = _snap({("accel", "real"): 100, ("accel", "padded"): 50})
    end = _snap({("accel", "real"): 164, ("accel", "padded"): 50,
                 ("cpu0", "real"): 30, ("cpu0", "padded"): 2})
    # 64 + 30 real and 0 + 2 padded rows in the window
    assert PADDED(_run(tel_start=start, tel_end=end)) \
        == pytest.approx(100 * 2 / 96)
    assert harness.load_reader("padded_row_share.fed4")(
        _run(tel_start=start, tel_end=end)) == pytest.approx(100 * 2 / 96)


@pytest.mark.parametrize("start, end", [
    ({}, {}),                                   # a program without them
    (_snap({("accel", "real"): 8}), _snap({("accel", "real"): 8})),
])
def test_padded_row_share_is_none_without_rows(start, end):
    assert PADDED(_run(tel_start=start, tel_end=end)) is None


@pytest.mark.parametrize("wait0, wait1, us", [
    (None, None, 24.0),                         # the step calls alone
    # the waits' CPU counts too: 0.024 + (0.020 - 0.005) + 0.001 s
    ({"accel": (2, 0.005)}, {"accel": (6, 0.020), "cpu0": (3, 0.001)},
     40.0),
])
def test_dispatch_cpu_per_token(wait0, wait1, us):
    start = _snap(cpu={"accel": (2, 0.010)}, wait=wait0)
    end = _snap(cpu={"accel": (6, 0.030), "cpu0": (3, 0.004)}, wait=wait1)
    run = _run(tel_start=start, tel_end=end, generated_tokens=1000)
    assert CPU(run) == pytest.approx(us)
    assert harness.load_reader("dispatch_cpu_us_per_token.fed4")(run) \
        == pytest.approx(us)


@pytest.mark.parametrize("start, end, tokens", [
    ({}, {}, 1000),                             # a program without it
    (_snap(cpu={"accel": (2, 0.01)}), _snap(cpu={"accel": (2, 0.01)}), 10),
    (_snap(), _snap(cpu={"accel": (2, 0.01)}), 0),
    # waits without a step call in the window
    (_snap(wait={"accel": (1, 0.01)}), _snap(wait={"accel": (3, 0.05)}), 10),
])
def test_dispatch_cpu_is_none_without_steps_or_tokens(start, end, tokens):
    run = _run(tel_start=start, tel_end=end, generated_tokens=tokens)
    assert CPU(run) is None


def _events(tracks, spans):
    """Chrome events as ``SpanTracer.chrome_events`` lays them out:
    thread-name rows, then spans; times in us."""
    tid = {name: i + 1 for i, name in enumerate(tracks)}
    meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": t,
             "args": {"name": name}} for name, t in tid.items()]
    return meta + [{"name": n, "cat": "service", "ph": "X", "ts": a,
                    "dur": b - a, "pid": 0, "tid": tid[track],
                    "args": args} for n, track, a, b, args in spans]


def test_fed_runtime_drained_share():
    spans = [
        ("serve", "serve", 100, 200, {"runtimes": ["r0", "r1"]}),
        # r0: busy 110..150 and 140..190 (overlapping): drained 20 of 100
        ("epoch:0", "r0/epochs", 110, 150, {}),
        ("epoch:1", "r0/epochs", 140, 190, {}),
        # r1: busy 120..160, and one epoch partly before the call: 40 + 5
        ("epoch:0", "r1/epochs", 120, 160, {}),
        ("epoch:3", "r1/epochs", 90, 105, {}),
        # outside the window's serve calls: ignored
        ("epoch:2", "r0/epochs", 300, 400, {}),
        ("serve.drain", "serve", 101, 199, {"parent": None}),
    ]
    run = _run(spans=_events(["serve", "r0/epochs", "r1/epochs"], spans),
               mono_window=(0.0, 1.0))
    # (20 + 55) drained of 2 x 100
    assert DRAINED(run) == pytest.approx(100 * 75 / 200)


def test_fed_runtime_drained_share_is_none_when_unreadable():
    spans = [("serve", "serve", 100, 200, {"runtimes": ["r0"]}),
             ("epoch:0", "r0/epochs", 110, 150, {})]
    events = _events(["serve", "r0/epochs"], spans)
    assert DRAINED(_run(spans=events, mono_window=(0.0, 1.0),
                        trace_dropped=1)) is None
    # a program that records no serve span
    assert DRAINED(_run(spans=events[1:2] + events[3:],
                        mono_window=(0.0, 1.0))) is None
    # a serve call outside the window
    assert DRAINED(_run(spans=events, mono_window=(0.0, 1e-4))) is None


def test_serve_programs_keep_the_names_the_reduction_matches():
    """The device-trace reduction finds the prefill and decode programs
    by their jitted names (``jit_prefill_fn``, ``jit_decode_fn``): a
    renamed program would fall out of the rooflines and ``mfu``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_reduced_config
    from repro.core.types import DeviceKind
    from repro.serve.engine import HeteroServeEngine
    from repro.train.trainer import GroupDef

    cfg = get_reduced_config("stablelm-1.6b").replace(n_layers=1)
    eng = HeteroServeEngine(cfg, [GroupDef("accel", DeviceKind.ACCEL)],
                            prompt_len=4, decode_tokens=2)
    prefill_fn, decode_fn = eng._fns_for(2)
    tokens = np.zeros((2, 4), np.int32)
    _, cache = prefill_fn(eng.params, tokens, None)
    lowered = {
        "prefill_fn": prefill_fn.lower(eng.params, tokens, None),
        "decode_fn": decode_fn.lower(eng.params, cache,
                                     jnp.zeros((2, 1), jnp.int32)),
    }
    assert set(lowered) == set(trace.PROGRAMS)
    for name, low in lowered.items():
        module = low.as_text().split("\n", 1)[0]
        assert f"@jit_{name} " in module, module
