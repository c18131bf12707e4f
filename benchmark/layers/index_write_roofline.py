"""Share of its roofline that ``jit(index_write)`` reaches: the least time the
chip's memory could take for the bytes a call needs
(``workcount_write.index_write_bytes`` at the mean number of padded slots a
call carried, from the program's ``index.write`` spans) over the program's mean
device time per call in the trace. The program moves kilobytes, so it is bound
by its launch and reads far under 1%."""

import sys

from lib import program_spans as ps, workcount_write, xplane


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in") or not cell.get("chip"):
        return None
    t = xplane.program_time(trace, "index_write")
    writes = ps.named(ps.load(cell), "index.write")
    padded = sum(w["args"].get("padded", 0) for w in writes)
    if not t or not padded:
        return None
    cfg = cell["config"]
    nbytes = workcount_write.index_write_bytes(
        padded / t["calls"], cfg["hidden_size"], cfg["metric"])
    least = nbytes / cell["chip"]["hbm_bytes_per_s"]
    print(f"[layer] index_write: {padded / t['calls']:.1f} padded slots a call, least "
          f"{least * 1e6:.3f} us, device {t['mean_s'] * 1e6:.1f} us a call over "
          f"{t['calls']} calls", file=sys.stderr)
    return 100.0 * least / t["mean_s"]
