"""Share of the traced window in which no operation ran on the device."""


def read(trace, spans, counts, cell):
    if not trace or trace.get("stand_in") or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
