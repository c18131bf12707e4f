"""Programs compiled inside the measured window (``backend_compile_duration``
events that were no cache hit). Warm-up covers every shape, so this should
read 0; when it does not, it is reported, not hidden."""


def read(trace, spans, counts, cell):
    return float(counts["compiles_in_window"])
