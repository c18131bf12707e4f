"""Share of the traced window in which a search had handed the device work
and the device was not busy: the ticks' device envelopes
(``lib/engine_time.py``) less the device trace's busy seconds, so launch and
transfer slack. The share of ``device_idle_pct`` that ties the program's spans
to the device trace."""

from lib import engine_time


def read(trace, spans, counts, cell):
    split = engine_time.partition(engine_time.spans_of(cell), cell.get("trace_window"))
    if not trace or split is None:
        return None
    return 100.0 * (split["envelope"] - trace["busy_s"]) / split["window"]
