"""A kernel's share of its roofline over a traced window."""
import sys


def share(run, kernel: str, work):
    """``work`` lists (operations, bytes) of every call the window made;
    the least time of each is the larger of operations over peak FLOP/s and
    bytes over peak bandwidth. Returns the percent of the kernel's traced
    time that this least time makes, or None when the trace holds no call
    of the kernel. Names the bound on standard error."""
    k = (run.trace or {}).get("kernels", {}).get(kernel)
    if not k or not k["seconds"] or not work:
        return None
    p = run.peak
    t_ops = sum(o for o, _ in work) / p["flops"]
    t_mem = sum(b for _, b in work) / p["hbm_bytes_per_s"]
    least = sum(max(o / p["flops"], b / p["hbm_bytes_per_s"]) for o, b in work)
    bound = "memory" if t_mem >= t_ops else "compute"
    print(f"{kernel}_roofline: {len(work)} step calls, {k['calls']} kernel "
          f"events, {k['seconds']:.6f} s; bound by {bound} "
          f"(operations {t_ops:.6f} s, bytes {t_mem:.6f} s at peak)",
          file=sys.stderr, flush=True)
    return 100.0 * least / k["seconds"]
