"""Migration: the integrity check's share of a state transfer. Time in
``arrow.migrate.checksum`` spans (the export side's and the import side's,
which never overlap) over time in ``arrow.migrate`` spans, in %."""
import program_trace


def read(run):
    tr = program_trace.of(run)
    if tr is None:
        return None
    moves = tr.named("arrow.migrate")
    total = sum(m.seconds for m in moves)
    if not total:
        return None
    sums = [s for s in tr.named("arrow.migrate.checksum")
            if any(program_trace.inside(s, m) for m in moves)]
    return 100.0 * sum(s.seconds for s in sums) / total
