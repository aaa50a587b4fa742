"""Model step: how full a decode batch is. The mean, over the traced
``arrow.dispatch`` spans of steps that carried decode rows, of the active
rows over the batch's slots (the span's ``rows`` and ``slots``), in %:
every decode step runs all slots, so the rest is paid for and unused."""
import program_trace


def read(run):
    tr = program_trace.of(run)
    if tr is None:
        return None
    fill = [s.args["rows"] / s.args["slots"] for s in tr.named("arrow.dispatch")
            if s.args.get("rows", 0) > 0]
    return 100.0 * sum(fill) / len(fill) if fill else None
