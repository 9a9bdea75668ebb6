"""The comparison that decides ``correct``: served answers against the reference.

Ids must be equal.  Lengths the program computes in float32 are compared
within ``LENGTH_M`` (a float32 metre at city scale is ~1 mm).  Traversal
times are compared within the trace's sampling interval (``reports_agree``
says why), at least ``TIME_S``; a queue length, a run of travel below the
queue speed, within ``QUEUE_M`` plus that speed times the interval.
"""

from __future__ import annotations

TIME_S = 0.05
LENGTH_M = 0.05
QUEUE_M = 0.5
QUEUE_MPS = 20.0 / 3.6  # the queue speed threshold


def _num_close(a, b, tol) -> bool:
    if a is None or b is None:
        return a is b
    if (a == -1) != (b == -1):
        return False
    return abs(float(a) - float(b)) <= tol


def _gap(trace: dict) -> float:
    """The trace's median sampling interval (s)."""
    t = sorted(float(p["time"]) for p in trace["trace"])
    d = sorted(b - a for a, b in zip(t, t[1:]))
    return d[len(d) // 2] if d else 0.0


def reports_agree(served, ref, gap: float) -> bool:
    """Datastore reports: ids and next ids equal, lengths to LENGTH_M, the
    traversal times to the trace's sampling interval.  An HMM decode is
    indifferent to where between two fixes a point lies on the route (the
    two route legs telescope), so a score tie may place a pin anywhere
    along it and move the interpolated times by up to one interval."""
    if len(served) != len(ref):
        return False
    tol_t = max(TIME_S, gap)
    tol_q = QUEUE_M + QUEUE_MPS * gap
    for s, r in zip(served, ref):
        if s.get("id") != r["id"] or s.get("next_id") != r.get("next_id"):
            return False
        if not (_num_close(s.get("t0"), r["t0"], tol_t)
                and _num_close(s.get("t1"), r["t1"], tol_t)
                and _num_close(s.get("length"), r["length"], LENGTH_M)
                and _num_close(s.get("queue_length"), r["queue_length"], tol_q)):
            return False
    return True


def answer_agrees(body: dict, trace: dict, ref) -> bool:
    """One /report answer against the reference's (segments, reports,
    shape_used): the reports as ``reports_agree`` says, and the consumed
    shape within one point."""
    _segs, reports, shape_used = ref
    try:
        su = body.get("shape_used")
        return (reports_agree(body["datastore"]["reports"], reports, _gap(trace))
                and (su is None) == (shape_used is None)
                and (su is None or abs(int(su) - int(shape_used)) <= 1))
    except (KeyError, TypeError, ValueError):
        return False


def _row_key(row):
    return (int(row[0]), int(row[1]), row[8], row[9])


def _row_close(a, b, gap: float) -> bool:
    tol_t = 1 + int(gap)  # whole seconds, floor/ceil of a time within gap
    return (abs(int(a[2]) - int(b[2])) <= tol_t and int(a[3]) == int(b[3])
            and abs(float(a[4]) - float(b[4])) <= LENGTH_M
            and abs(float(a[5]) - float(b[5])) <= QUEUE_M + QUEUE_MPS * gap
            and abs(int(a[6]) - int(b[6])) <= tol_t
            and abs(int(a[7]) - int(b[7])) <= tol_t)


def index_rows(tiles: dict) -> dict:
    """{tile path: [csv row, ...]} -> {(path, key): [fields, ...]} for lookup."""
    out = {}
    for path, lines in tiles.items():
        for line in lines:
            f = line.strip().split(",")
            if len(f) == 10:
                out.setdefault((path, _row_key(f)), []).append(f)
    return out


def rows_missing(index: dict, ref_rows, trace: dict) -> int:
    """How many of one window's reference (path, row) tile rows the program
    did not write; each program row answers for one reference row at most,
    times within the window's sampling interval (see ``reports_agree``)."""
    gap = _gap(trace)
    missing = 0
    for path, row in ref_rows:
        pool = index.get((path, _row_key(row)), [])
        hit = next((i for i, f in enumerate(pool) if _row_close(f, row, gap)), None)
        if hit is None:
            missing += 1
        else:
            pool.pop(hit)
    return missing
