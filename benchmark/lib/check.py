"""The comparison that decides ``correct``: what the timed path answered,
against the plain reference. numpy only; the child hands it the reference's
vectors and rankings, the parent hands it the replies.

Numbers compared (each against a limit of its own, ``benchmark/limits/``):

- ``rank_gap``: over the sampled requests and the ranks of each reply, the
  widest gap by which the reference's score of the row answered at a rank lies
  below the reference's own best at that rank (rows of documents the writer
  touches in the window are left out on both sides: their state at the moment
  of the search is not defined);
- ``score_err``: the widest difference between a score the server returned
  and the reference's score of that very row and version;
- ``bad_replies``: replies that are no 200, not ``k`` rows, unparsable, a row or
  version that never existed, or one row twice (limit 0); a request confined
  to a folder is answered whole by ``k`` rows where the folder holds ``k`` live
  rows, else by every live row of it, no more;
- scoped mixes: ``out_of_scope`` (rows answered that lie outside their
  request's folder, over all replies; limit 0), and ``rank_gap`` is taken
  against the reference's best over the folder's rows;
- live mixes: ``order_violations`` (a row retired by a write that an earlier
  reply had already shown applied; limit 0), ``lost_writes`` (never seen a
  minute past the close; limit 0), ``count_off`` (rows the store counts after
  the window and a quiet second, less the expected; limit 0).
"""

from __future__ import annotations

import numpy as np

from . import traffic


def reply_rows(body, fewest: int, most: int):
    """[(doc, chunk, ver, score)] of one reply, or None if it is malformed or
    holds fewer rows than ``fewest`` or more than ``most``."""
    if not isinstance(body, list) or not fewest <= len(body) <= most:
        return None
    out, seen = [], set()
    for hit in body:
        row = traffic.parse_row_text(hit.get("text")) if isinstance(hit, dict) else None
        if row is None or row[:2] in seen or "dist" not in hit:
            return None
        seen.add(row[:2])
        out.append((*row, -float(hit["dist"])))
    return out


def order_violations(replies: list[dict], plan: "traffic.WriterPlan | None") -> int:
    """Replies that show a row a write had retired, sent after another reply
    had already shown that write (or a later one) applied."""
    if plan is None:
        return 0
    seen = []  # (recv, highest commit index shown)
    for r in replies:
        hi = max((plan.written_by.get((d, v), -1) for d, _, v, _ in r["rows"]),
                 default=-1)
        if hi >= 0:
            seen.append((r["recv"], hi))
    seen.sort()
    recvs = np.array([s[0] for s in seen])
    run_hi = np.maximum.accumulate(np.array([s[1] for s in seen])) if seen else []
    bad = 0
    for r in replies:
        ix = int(np.searchsorted(recvs, r["send"], side="right")) - 1
        if ix < 0:
            continue
        shown = int(run_hi[ix])
        if any(plan.retired_by.get((d, v), 1 << 60) <= shown for d, _, v, _ in r["rows"]):
            bad += 1
    return bad


def out_of_scope(replies: list[dict], folder_of_doc: np.ndarray) -> int:
    """Rows answered that are not of the folder their request was confined to,
    over the replies given; a request with no filter has none."""
    docs = len(folder_of_doc)
    return sum(not (0 <= d < docs and folder_of_doc[d] == r["scope"])
               for r in replies if r["scope"] is not None
               for d, *_ in r["rows"])


def first_seen(replies: list[dict], plan: "traffic.WriterPlan") -> list[float | None]:
    """For each commit, when the first reply arrived that shows it applied: a
    row of that commit, or of a later one (the connector's stream is ordered)."""
    n = len(plan.commits)
    at = np.full(n + 1, np.inf)
    for r in replies:
        hi = max((plan.written_by.get((d, v), -1) for d, _, v, _ in r["rows"]),
                 default=-1)
        if hi >= 0:
            at[hi] = min(at[hi], r["recv"])
    at = np.minimum.accumulate(at[::-1])[::-1]  # a later commit shown shows this one
    out = []
    for c in plan.commits:
        # a delete writes no row: the next commit's row shows it
        t = at[c.index + 1] if c.ver is None else at[c.index]
        out.append(None if not np.isfinite(t) else float(t))
    return out


def compare_sample(sample: list[dict], ref_vec: dict[str, np.ndarray],
                   ref_top: dict[tuple, np.ndarray], row_vector, unstable: set[int]):
    """(rank_gap, score_err, bad) over the sampled replies. ``ref_top[(text,
    scope)]`` is the reference's best-first scores over the stable rows of the
    request's folder (scope None: of the store); ``row_vector(doc, chunk, ver)``
    is the vector that version of the row was written with, or None if it never
    existed."""
    rank_gap = score_err = 0.0
    bad = 0
    for r in sample:
        q = ref_vec[r["query"]].astype(np.float64)
        top = ref_top[(r["query"], r["scope"])]
        j = 0
        for doc, chunk, ver, score in r["rows"]:
            vec = row_vector(doc, chunk, ver)
            if vec is None:
                bad += 1
                continue
            ref = float(q @ vec.astype(np.float64))
            score_err = max(score_err, abs(score - ref))
            if doc not in unstable:
                rank_gap = max(rank_gap, float(top[j]) - ref)
                j += 1
    return rank_gap, score_err, bad


def decide(numbers: dict[str, float], limits: dict[str, float]):
    """(correct, [(name, number, limit)]): every number a limit, every limit a
    number; a number over its limit, or missing, is not correct."""
    table, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        table.append((name, value, limit))
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, table
