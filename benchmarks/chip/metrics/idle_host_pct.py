"""Device: idle time that the host's own work holds the device in. The
time with no op on the device while the host is inside an ``arrow.*`` span
(a pass of the cluster's step loop, a migration, ...), over the window's
length, in %, averaged over devices. Idle time outside every span is time
with no work to do, which the load generator's ``bench.wait`` names."""
import program_trace


def read(run):
    tr = program_trace.of(run)
    if tr is None or not tr.spans or not tr.devices:
        return None
    host = [(s.start, s.end) for s in tr.spans]
    idle = [program_trace.minus(host, ops)
            for ops in tr.devices.values()]
    return 100.0 * sum(idle) / len(idle) * 1e-9 / (run.t1 - run.t0)
