"""Generated tokens of the window's completed jobs over the window: from
the first wave's submit to the last wave's end, on the host clock."""


def read(run):
    if run.window_s <= 0:
        return None
    return run.generated_tokens / run.window_s
