"""Model step: model operations of the window's instance steps (every
token processed, prompt and output alike: ``flops.token_flops`` and
``flops.prefill_flops`` on the live lengths) over the sum of their times
from dispatch to tokens fetched, times the chip's peak: how much of the
peak a step reaches while one is running."""
import flops


def read(run):
    c = run.cell.run_cfg
    total = busy = 0.0
    for s in run.window_steps:
        total += sum(flops.token_flops(c, n) for n in s.decode_ctx)
        total += sum(flops.prefill_flops(c, o, n) for o, n in s.chunks)
        if s.t_ready is not None:
            busy += s.t_ready - s.t0
    if not total or not busy:
        return None
    return 100.0 * total / (busy * run.peak["flops"])
