"""Serving API: the share of the window's requests that met both limits
of the mix, time to first token from the due time and time per output
token; a request that never finished missed them."""


def read(run):
    slo = run.cell.mix["slo"]
    met = 0
    for lg in run.reqs:
        if not lg.done or lg.first is None:
            continue
        n = len(lg.tokens)
        tpot = (lg.last - lg.first) / (n - 1) if n > 1 else 0.0
        if lg.first - lg.due <= slo["ttft_s"] and tpot <= slo["tpot_s"]:
            met += 1
    return 100.0 * met / len(run.reqs) if run.reqs else None
