"""Kernels: the SSD scan's share of its roofline. The least time for the
window's prompt chunks (``flops.ssd_scan`` on each chunk's live length)
over the kernel's time in the trace."""
import roofline


def read(run):
    c = run.cell.run_cfg
    import flops
    work = [flops.ssd_scan(c, n) for s in run.window_steps for _, n in s.chunks]
    return roofline.share(run, "ssd_scan", work)
