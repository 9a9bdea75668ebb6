"""Kernels: the UBODT probe's share of its HBM roofline in the traced stretch.

The probe is a gather of whole bucket rows out of the table; it does no
arithmetic worth counting, so its least time is the bytes of the rows it
must read over the chip's HBM bandwidth (``peaks.json``).  Those bytes are
counted from the shapes the trace shows: every ``rs.ubodt-probe`` gather (or
fusion around one) whose output is a 2-D [rows, width] array taken out of a
2-D [buckets, width] operand of the same width (the table) reads rows x
width elements.  That is the count of ``executed_rows`` below (a copy of
``reporter_tpu/obs/attrib.py``'s row-count cost model) over the shapes
dispatched: on the recorded v5e trace both give 23,223,808 rows
(``tests/test_trace_reduce.py`` holds an excerpt).  A probe lowered so that
no such gather shows any more reads nothing here, and the metric is left out.
Share = (bytes / peak bytes/s) / the stage's device time, in %.
"""

import re

SHAPE = re.compile(r"([a-z]+)(\d+)\[([\d,]*)\]")
CALL = re.compile(r"\s([a-z][\w.-]*)\((.*)")


def _dims(text):
    m = SHAPE.match(text.strip())
    if not m:
        return None
    return m.group(1) + m.group(2), [int(d) for d in m.group(3).split(",") if d]


def executed_rows(n_pairs: int, max_probes: int) -> int:
    """Bucket rows a dispatch of ``n_pairs`` probe pairs gathers: every
    pair probes ``max_probes`` rows (2 for the cuckoo layout).  The program's
    probe dedup, off in these configurations, would gather a fixed budget."""
    return max_probes * n_pairs


def row_bytes(long_name: str) -> int:
    """Bytes of table rows one execution of the op reads, 0 if it is no row
    gather out of a [buckets, width] table."""
    try:
        lhs, rhs = long_name.split(" = ", 1)
        out = _dims(rhs)
        call = CALL.search(rhs)
        first = _dims(call.group(2))
    except (ValueError, AttributeError):
        return 0
    if not out or not first:
        return 0
    (dt, od), (_dt2, fd) = out, first
    if (call.group(1) not in ("gather", "fusion") or len(od) != 2 or len(fd) != 2
            or od[1] != fd[1] or fd[0] < 1024 or od[0] == fd[0]):
        return 0
    return od[0] * od[1] * int(SHAPE.match(dt + "[]").group(2)) // 8


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["stages_s"].get("ubodt-probe"):
        return None
    peak = rec["peaks"][rec["device"]["kind"]]["hbm_bytes_per_s"]
    nbytes = sum(row_bytes(o["args"].get("long_name", "")) * o["count"]
                 for o in tr["ops"].values() if o["stage"] == "ubodt-probe")
    if nbytes == 0:
        return None
    return 100.0 * (nbytes / peak) / tr["stages_s"]["ubodt-probe"]
