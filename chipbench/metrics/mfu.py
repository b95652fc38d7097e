"""FLOPs of every prompt and generated token the traced window's chunks
completed, over the traced window times the chips times the chip's peak
bf16 FLOP/s."""


def read(run):
    if run.trace is None or run.peaks is None or run.trace.window_s <= 0:
        return None
    flops = sum(w.flops for w in run.work.values())
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["flops_bf16"])
