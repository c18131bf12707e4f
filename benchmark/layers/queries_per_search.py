"""Mean number of queries a wrapped ``BruteForceKnnEngine.search`` call
carried in the window: how many requests the dataflow tick batched."""


def read(trace, spans, counts, cell):
    q = [s["q"] for s in spans if s["name"] == "search"]
    return sum(q) / len(q) if q else None
