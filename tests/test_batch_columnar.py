"""The batch pipeline's columnar phase 2.

A shard file's points stay in arrays from the parser to the matcher, and a
micro-batch's tile rows come out of one flat pass.  The per-point read loop
and the per-observation tile fan-out they replaced live on here as oracles:
the requests, the matcher's columns and the tile rows must come out the
same, value for value and in the same order.
"""

import json
import math
import os
import unittest.mock as mock

import numpy as np
import pytest

from reporter_tpu import native
from reporter_tpu.anonymise.tiles import (
    observations_for_report,
    tile_rows,
    usable_report,
)
from reporter_tpu.batch import pipeline
from reporter_tpu.obs import metrics as obs
from reporter_tpu.tiles.segment_id import INVALID_SEGMENT_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E2E_FIXTURE = os.path.join(REPO, "tests", "fixtures", "batch_columnar_tiles.json")
E2E_QUANTISATIONS = (3600, 300)


# -- the oracles: the per-point read and the per-observation fan-out ----------


def _old_parse(data, lib):
    """Per-row parse: every row's uuid decoded from its own slice."""
    n_lines = data.count(b"\n") + 1
    if lib is not None:
        lat = np.empty(n_lines, np.float64)
        lon = np.empty(n_lines, np.float64)
        tm = np.empty(n_lines, np.int64)
        acc = np.empty(n_lines, np.int32)
        uoff = np.empty(n_lines, np.int64)
        ulen = np.empty(n_lines, np.int32)
        n = lib.rn_parse_shard(data, len(data), lat, lon, tm, acc, uoff, ulen,
                               n_lines)
        uuids = [data[uoff[i]: uoff[i] + ulen[i]].decode(errors="replace")
                 for i in range(n)]
        return uuids, tm[:n], lat[:n], lon[:n], acc[:n]
    uuids, tms, lats, lons, accs = [], [], [], [], []
    for line in data.decode(errors="replace").splitlines():
        try:
            uuid, tm_, lat_, lon_, acc_ = line.strip().split(",")
            if not uuid:
                continue
            row = (int(tm_), float(lat_), float(lon_), int(acc_))
        except ValueError:
            continue
        uuids.append(uuid)
        tms.append(row[0])
        lats.append(row[1])
        lons.append(row[2])
        accs.append(row[3])
    return (uuids, np.asarray(tms, np.int64), np.asarray(lats, np.float64),
            np.asarray(lons, np.float64), np.asarray(accs, np.int32))


def _old_windows(points, inactivity):
    starts = [i for i, p in enumerate(points)
              if i == 0 or p["time"] - points[i - 1]["time"] > inactivity]
    for idx, i in enumerate(starts):
        j = starts[idx + 1] if idx + 1 < len(starts) else len(points)
        if j - i >= 2:
            yield points[i:j]


def _old_read(path, inactivity, mode, lib):
    """The whole file in one parse, a dict of point dicts per uuid, each
    list sorted by time and split at inactivity gaps."""
    with open(path, "rb") as f:
        data = f.read()
    tail = data[data.rfind(b"\n") + 1:]
    lines = data.count(b"\n") + (1 if tail.strip() else 0)
    uuids, tms, lats, lons, accs = _old_parse(data, lib)
    traces = {}
    for i in range(len(uuids)):
        traces.setdefault(uuids[i], []).append(
            {"lat": float(lats[i]), "lon": float(lons[i]),
             "time": int(tms[i]), "accuracy": int(accs[i])})
    requests = []
    for uuid, points in traces.items():
        points.sort(key=lambda v: v["time"])
        for window in _old_windows(points, inactivity):
            requests.append({"uuid": uuid, "trace": window,
                             "match_options": {"mode": mode}})
    return requests, lines - len(uuids)


def _old_bucket(pairs, quantisation, source, vehicle_type, tiles):
    spilled = []
    for request, rep in pairs:
        points = request["trace"]
        max_buckets = (points[-1]["time"] - points[0]["time"]) \
            // quantisation + 1
        for r in rep["datastore"]["reports"]:
            if not usable_report(r):
                continue
            emitted = False
            for tile, ob in observations_for_report(
                    r, quantisation, source, vehicle_type=vehicle_type,
                    max_buckets=max_buckets):
                tiles.setdefault(tile.path(quantisation), []).append(
                    ob.csv_row() + "\n")
                emitted = True
            if not emitted:
                spilled.append((request["uuid"], max_buckets))
    return spilled


# -- seeded shard files --------------------------------------------------------


T0 = 1_460_000_000


def _shard_rows(seed, inactivity):
    """Rows (as bytes) of six vehicles: fixes at mixed steps with repeated
    timestamps, gaps of exactly ``inactivity`` and one second over it, lone
    fixes between gaps, a multi-byte uuid, and two uuids whose invalid
    bytes decode alike.  The rows are shuffled into a seeded order, so the
    vehicles interleave and equal timestamps land in either order."""
    rng = np.random.default_rng(seed)
    gap = int(inactivity)
    names = [b"veh-a", b"veh-b", "véh-ç".encode(), b"x\xff",
             b"x\xfe", b"a-much-longer-vehicle-uuid-0123456789"]
    rows = []
    for k, name in enumerate(names):
        t = T0 + 7 * k
        for _ in range(int(rng.integers(6, 30))):
            step = int(rng.choice([0, 1, 15, 60, gap, gap + 1, 5 * gap]))
            t += step
            rows.append(b"%s,%d,%.7f,%.7f,%d\n" % (
                name, t, 37.7 + rng.random() * 0.1, -122.5 + rng.random() * 0.1,
                int(rng.integers(1, 60))))
    rows = [rows[i] for i in rng.permutation(len(rows))]
    # torn rows: a line cut short, a uuid cut inside a multi-byte character
    # (a row of its own), and a row with no uuid
    rows.insert(int(rng.integers(0, len(rows))), b"veh-a,14600\n")
    rows.insert(int(rng.integers(0, len(rows))),
                b"v\xc3,%d,37.75,-122.45,5\n" % (T0 + 3))
    rows.insert(int(rng.integers(0, len(rows))), b",%d,37.75,-122.45,5\n" % T0)
    return rows


def _write_shard(path, seed, inactivity, crlf=False, torn_tail=False):
    rows = _shard_rows(seed, inactivity)
    data = b"".join(rows)
    if crlf:
        data = data.replace(b"\n", b"\r\n")
    if torn_tail:
        data += b"veh-b,1460"          # a torn last row with no newline
    with open(path, "wb") as f:
        f.write(data)


def _read(path, inactivity, mode, chunk_bytes):
    """(requests, rows counted as skipped) of the columnar read."""
    name = "reporter_batch_rows_skipped_total"
    before = _counter(obs.REGISTRY.snapshot(), name)
    got = pipeline._read_requests(path, inactivity, mode,
                                  chunk_bytes=chunk_bytes)
    return got, _counter(obs.REGISTRY.snapshot(), name) - before


def _counter(snap, name):
    return sum(v for _l, v in (snap.get(name) or {}).get("samples", []))


def _assert_same_requests(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        cols = g.pop("_columns")
        assert list(g) == list(w)
        # repr keeps the Python types: 1.0 and 1 read differently
        assert repr(g) == repr(w)
        pts = g["trace"]
        for key, dtype_vals in (("lat", [p["lat"] for p in pts]),
                                ("lon", [p["lon"] for p in pts]),
                                ("time", [float(p["time"]) for p in pts])):
            assert cols[key].dtype == np.float64
            np.testing.assert_array_equal(cols[key], np.array(dtype_vals))


@pytest.fixture(params=["native", "python"])
def parser_lib(request):
    if request.param == "native":
        lib = native.get_lib()
        if lib is None:
            pytest.skip("native library unavailable")
        return lib
    return None


@pytest.mark.parametrize("chunk_bytes", [1 << 26, 97, 7])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_read_matches_per_point_loop(tmp_path, parser_lib, seed, chunk_bytes):
    path = str(tmp_path / "shard")
    _write_shard(path, seed, 120.0, torn_tail=seed == 1)
    want, want_skipped = _old_read(path, 120.0, "auto", parser_lib)
    with mock.patch.object(native, "get_lib", lambda: parser_lib):
        got, skipped = _read(path, 120.0, "auto", chunk_bytes)
    assert want and skipped == want_skipped >= 2
    _assert_same_requests(got, want)


@pytest.mark.parametrize("inactivity", [0.0, 59.5, 60.0, 3600.0])
def test_read_splits_like_per_point_loop(tmp_path, parser_lib, inactivity):
    """Gaps of exactly the limit stay in one window, a second over splits,
    a fractional limit splits like the float comparison, and 1-point
    windows are dropped."""
    path = str(tmp_path / "shard")
    _write_shard(path, 5, max(inactivity, 1.0), crlf=True)
    want, want_skipped = _old_read(path, inactivity, "bus", parser_lib)
    with mock.patch.object(native, "get_lib", lambda: parser_lib):
        got, skipped = _read(path, inactivity, "bus", 64)
    assert skipped == want_skipped
    _assert_same_requests(got, want)


def test_read_counts_torn_rows(tmp_path):
    path = str(tmp_path / "shard")
    _write_shard(path, 3, 120.0, torn_tail=True)
    requests, skipped = _read(path, 120.0, "auto", 50)
    # the cut line, the row with no uuid and the torn tail
    assert skipped == 3 and requests


def test_read_empty_file(tmp_path):
    path = str(tmp_path / "shard")
    open(path, "wb").close()
    assert _read(path, 120.0, "auto", 1 << 26) == ([], 0)


# -- the tile-row pass ---------------------------------------------------------


def _random_pairs(rng, n_requests=40):
    pairs = []
    for k in range(n_requests):
        t_first = float(rng.integers(1, 50_000))
        span = float(rng.choice([0, 30, 600, 3000, 20_000]))
        request = {"uuid": "u%d" % k,
                   "trace": [{"time": int(t_first)},
                             {"time": int(t_first + span)}]}
        reports = []
        for _ in range(int(rng.integers(0, 6))):
            t0 = t_first + float(rng.integers(-100, 3000))
            kind = rng.integers(0, 6)
            if kind == 0:      # an exact half-second duration
                t0 = math.floor(t0) + 0.25
                t1 = t0 + int(rng.integers(1, 400)) + 0.5
            elif kind == 1:    # whole-second ints
                t0 = int(t0)
                t1 = t0 + int(rng.integers(1, 5000))
            elif kind == 2:    # spans many buckets: past the guard, often
                t1 = t0 + float(rng.integers(2000, 40_000)) + rng.random()
            else:
                t0 = t0 + rng.random()
                t1 = t0 + rng.random() * 900
            r = {"id": int(rng.integers(0, 1 << 46)), "t0": t0, "t1": t1,
                 "length": (int(rng.integers(1, 900)) if rng.random() < 0.3
                            else round(float(rng.random() * 900), 3)),
                 "queue_length": (0 if rng.random() < 0.3
                                  else round(float(rng.random() * 50), 1))}
            u = rng.random()
            if u < 0.3:
                r["next_id"] = int(rng.integers(0, 1 << 46))
            elif u < 0.4:
                r["next_id"] = INVALID_SEGMENT_ID
            # unusable: t0 not positive, too short, no length, negative queue
            bad = rng.integers(0, 12)
            if bad == 0:
                r["t0"] = 0
            elif bad == 1:
                r["t1"] = r["t0"] + 0.25
            elif bad == 2:
                r["length"] = 0
            elif bad == 3:
                r["queue_length"] = -1
            elif bad == 4:
                del r["queue_length"]
            reports.append(r)
        pairs.append((request, {"datastore": {"reports": reports}}))
    return pairs


@pytest.mark.parametrize("quantisation", [60, 900, 3600])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_tile_rows_match_observations(seed, quantisation):
    pairs = _random_pairs(np.random.default_rng(seed))
    want, got = {}, {}
    want_spilled = _old_bucket(pairs, quantisation, "SRC", "AUTO", want)
    spilled = tile_rows(pairs, quantisation, "SRC", "AUTO", got)
    assert list(got.items()) == list(want.items())
    assert [(req["uuid"], mb) for req, mb in spilled] == want_spilled
    assert sum(map(len, want.values())) > 20
    if quantisation == 60:
        assert want_spilled, "the max_buckets guard never tripped"


def test_tile_rows_keep_window_order():
    """Rows land per path in the order of the pairs, then of the reports."""
    req = {"uuid": "u", "trace": [{"time": 100}, {"time": 400}]}
    reps = [{"datastore": {"reports": [
        {"id": 8, "t0": 100.0 + k, "t1": 120.5 + k, "length": 10.0,
         "queue_length": 0.0}]}} for k in range(3)]
    tiles = {}
    assert tile_rows([(req, r) for r in reps], 3600, "S", "AUTO", tiles) == []
    assert tiles == {"0_3599/0/1": [
        "8,%d,%d,1,10.0,0.0,%d,%d,S,AUTO\n" % (INVALID_SEGMENT_ID, 21, 100 + k,
                                               121 + k)
        for k in range(3)]}


# -- make_matches end to end ---------------------------------------------------


def _e2e_archive(matcher, root):
    """One shard file of five vehicles' drives on the grid city: drives
    joined by gaps of exactly the inactivity limit or split by one just
    over it, a repeated timestamp, a lone last fix and torn rows; the rows
    shuffled into a seeded order."""
    from reporter_tpu.synth.generator import TraceSynthesizer

    synth = TraceSynthesizer(matcher.arrays, seed=11)
    rng = np.random.default_rng(11)
    rows = []
    for v in range(5):
        uuid = b"e2e-%d" % v
        t = T0 + 97 * v
        for d, gap in enumerate((120, 121, 5400)):
            st = synth.synthesize(8 + 3 * v + 2 * d, dt=15.0, sigma=3.0,
                                  uuid=uuid.decode(), t0=float(t))
            pts = st.trace["trace"]
            rows += [b"%s,%d,%.7f,%.7f,%d\n" % (
                uuid, int(p["time"]), p["lat"], p["lon"], p["accuracy"])
                for p in pts]
            if d == 1:
                p = pts[len(pts) // 2]
                rows.append(b"%s,%d,%.7f,%.7f,%d\n" % (
                    uuid, int(p["time"]), p["lat"] + 1e-5, p["lon"],
                    p["accuracy"]))
            t = int(pts[-1]["time"]) + gap
        rows.append(b"%s,%d,%.7f,%.7f,5\n" % (uuid, t, pts[0]["lat"],
                                               pts[0]["lon"]))
    rows = [rows[i] for i in rng.permutation(len(rows))]
    rows.insert(7, b"e2e-1,14600\n")
    rows.insert(19, b"e2\xc3,%d,37.75,-122.45,5\n" % T0)
    os.makedirs(root, exist_ok=True)
    with open(os.path.join(root, "shard0"), "wb") as f:
        f.write(b"".join(rows))


def _e2e_tiles(matcher, root, dest, quantisation):
    pipeline.make_matches(root, matcher, dest_dir=dest, microbatch=4,
                          quantisation=quantisation,
                          report_levels={0, 1, 2}, transition_levels={0, 1, 2},
                          source="E2E")
    out = {}
    for r, _d, fs in os.walk(dest):
        for name in fs:
            path = os.path.join(r, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, dest)] = f.read().decode()
    return dict(sorted(out.items()))


@pytest.fixture(scope="module")
def e2e_matcher():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import grid_city

    return SegmentMatcher(network=grid_city(rows=5, cols=5, spacing_m=150.0),
                          config=MatcherConfig(), backend="jax")


@pytest.mark.parametrize("quantisation", E2E_QUANTISATIONS)
def test_make_matches_tile_files_unchanged(e2e_matcher, tmp_path, quantisation):
    """The tile files hold the bytes the per-point read and the
    per-observation fan-out wrote (the fixture was recorded from them), and
    every window went to the matcher with its columns."""
    _e2e_archive(e2e_matcher, str(tmp_path / "arch"))
    before = obs.REGISTRY.snapshot()
    got = _e2e_tiles(e2e_matcher, str(tmp_path / "arch"), str(tmp_path / "out"),
                     quantisation)
    after = obs.REGISTRY.snapshot()
    with open(E2E_FIXTURE) as f:
        want = json.load(f)[str(quantisation)]
    assert sum(s.count("\n") for s in want.values()) > 20
    assert got == want
    matched = (_counter(after, "reporter_batch_windows_matched_total")
               - _counter(before, "reporter_batch_windows_matched_total"))
    columnar = (_counter(after, "reporter_batch_columnar_windows_total")
                - _counter(before, "reporter_batch_columnar_windows_total"))
    assert matched >= 10 and columnar == matched
