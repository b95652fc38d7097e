"""CPU time the dispatcher threads spent on each chunk's programs, per
generated token: the sums of the program's ``exec.issue_cpu_s`` (inside
the step call) and ``exec.wait_cpu_s`` (inside the wait for the chunk's
outputs) histograms, both ``time.thread_time`` differences, differenced
between the snapshots before and after the window, over the tokens
generated, in us. Unlike ``step_wall_us_per_token`` it leaves out the
time a thread sleeps behind the device; counting the wait as well keeps
a wait that moves out of the step call counted. None where the program
has no issue histogram."""


def _seconds(snap):
    n = s = 0.0
    for key, h in snap.get("histograms", {}).items():
        if key.startswith("exec.issue_cpu_s"):
            n += h["count"]
            s += h["sum"]
        elif key.startswith("exec.wait_cpu_s"):
            s += h["sum"]
    return n, s


def read(run):
    n0, s0 = _seconds(run.tel_start)
    n1, s1 = _seconds(run.tel_end)
    if n1 <= n0 or not run.generated_tokens:
        return None
    return 1e6 * (s1 - s0) / run.generated_tokens
