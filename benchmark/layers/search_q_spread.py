"""90th less 10th percentile of ``q`` over the traced ``index.search`` spans:
which steady state the closed loop sat in (0 for cohorts of 8 + 8, 4 for
6 + 10, 14 for 15 + 1), which the metrics read by cohort move with."""

import numpy as np
from lib import engine_time
from lib import program_spans as ps


def read(trace, spans, counts, cell):
    q = [s["args"]["q"] for s in ps.named(engine_time.spans_of(cell), "index.search")
         if "q" in s["args"]]
    return float(np.percentile(q, 90) - np.percentile(q, 10)) if q else None
