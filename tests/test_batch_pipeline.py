"""Batch pipeline: phase semantics and an end-to-end archive -> tiles run."""

import glob
import gzip
import os

import pytest

from reporter_tpu.batch.pipeline import (
    LocalArchive,
    _cull_lines,
    _read_requests,
    compile_valuer,
    get_traces,
    make_matches,
    report_tiles,
    run_pipeline,
    split,
)


def test_split_balanced():
    assert split(list(range(10)), 3) == [[0, 1, 2, 3], [4, 5, 6], [7, 8, 9]]
    assert split([], 3) == [[], [], []]
    assert sum(split(list(range(17)), 4), []) == list(range(17))


def test_default_valuer():
    v = compile_valuer(None)
    line = "2017-01-01 06:05:40|veh-9|x|x|x|6.5|x|x|x|3.465725|-76.5135033"
    uuid, tm, lat, lon, acc = v(line)
    assert uuid == "veh-9" and tm == "2017-01-01 06:05:40"
    assert lat == "3.465725" and lon == "-76.5135033" and acc == "6.5"


def test_windows_inactivity_split(tmp_path):
    shard = tmp_path / "shard"
    shard.write_text("".join("veh-1,%d,37.75,-122.45,5\n" % t
                             for t in (0, 10, 20, 200, 210, 500)))
    wins = _read_requests(str(shard), 120, "auto")
    # the lone trailing point is dropped (<2 points)
    assert [len(w["trace"]) for w in wins] == [3, 2]
    assert wins[1]["trace"][0]["time"] == 200


def _row(sid, nid, t0=100):
    return "%d,%d,10,1,50.0,0.0,%d,%d,SRC,AUTO\n" % (sid, nid, t0, t0 + 10)


def test_cull_lines():
    lines = [_row(1, 2), _row(1, 2, 200), _row(3, 4)]
    kept = _cull_lines(lines, 2)
    assert len(kept) == 2 and all(k.startswith("1,2,") for k in kept)
    assert len(_cull_lines([_row(3, 4)], 1)) == 1
    # malformed rows are dropped, not fatal
    assert _cull_lines(["garbage\n"], 1) == []


def test_get_traces_shards_and_bbox(tmp_path):
    arch = tmp_path / "arch"
    arch.mkdir()
    lines = [
        "2017-01-01 06:05:40|veh-1|||||||.|37.75|-122.45",
        "2017-01-01 06:05:50|veh-1|||||||.|37.76|-122.44",
        "2017-01-01 06:05:40|veh-2|||||||.|10.0|10.0",  # outside bbox
    ]

    def fix(line):  # put accuracy in col 5
        parts = line.split("|")
        parts[5] = "4.2"
        return "|".join(parts)

    with gzip.open(str(arch / "day1.gz"), "wt") as f:
        f.write("\n".join(fix(l) for l in lines) + "\n")
    out = get_traces(
        str(arch),
        bbox=(37.0, -123.0, 38.0, -122.0),
        dest_dir=str(tmp_path / "traces"),
    )
    shards = os.listdir(out)
    assert len(shards) == 1 and len(shards[0]) == 3  # one uuid -> one 3-hex shard
    rows = open(os.path.join(out, shards[0])).read().strip().split("\n")
    assert len(rows) == 2
    uuid, tm, lat, lon, acc = rows[0].split(",")
    assert uuid == "veh-1" and tm == "1483250740" and acc == "5"


def test_local_archive_keys(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "x.gz").write_bytes(b"")
    (tmp_path / "b.txt").write_text("")
    arch = LocalArchive(str(tmp_path))
    assert arch.keys() == [os.path.join("a", "x.gz"), "b.txt"]
    assert arch.keys(key_regex=r".*\.gz") == [os.path.join("a", "x.gz")]


@pytest.fixture(scope="module")
def grid_matcher():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import grid_city

    return SegmentMatcher(
        network=grid_city(rows=5, cols=5, spacing_m=150.0),
        config=MatcherConfig(),
        backend="jax",
    )


def _write_archive(matcher, root, n_vehicles=3, n_points=24):
    from reporter_tpu.synth.generator import TraceSynthesizer

    os.makedirs(root, exist_ok=True)
    synth = TraceSynthesizer(matcher.arrays, seed=3)
    with open(os.path.join(root, "day0"), "w") as f:
        for v in range(n_vehicles):
            st = synth.synthesize(n_points, dt=15.0, sigma=3.0, uuid="veh-%d" % v)
            for p in st.trace["trace"]:
                f.write(
                    "veh-%d|%d|%.7f|%.7f|%d\n"
                    % (v, int(p["time"]), p["lat"], p["lon"], p["accuracy"])
                )


def test_batch_end_to_end(grid_matcher, tmp_path):
    _write_archive(grid_matcher, str(tmp_path / "arch"))
    out = str(tmp_path / "out")
    trace_dir, match_dir = run_pipeline(
        grid_matcher,
        archive_spec=str(tmp_path / "arch"),
        dest_store="dir:" + out,
        cleanup=False,
        valuer='lambda l: tuple(l.split("|"))',
        time_pattern=None,
        report_levels={0, 1, 2},
        transition_levels={0, 1, 2},
        privacy=1,
        source="CI",
        quantisation=3600,
    )
    assert trace_dir and match_dir
    # shard files exist and tile files were culled+uploaded with the header
    uploaded = glob.glob(os.path.join(out, "*", "*", "*", "*"))
    assert uploaded, "no tiles uploaded"
    for f in uploaded:
        lines = open(f).read().strip().split("\n")
        assert lines[0].startswith("segment_id,next_segment_id,")
        assert len(lines) > 1
        # rows: id,next_id,duration,count,length,queue,min,max,source,mode
        parts = lines[1].split(",")
        assert parts[3] == "1" and parts[8] == "CI" and parts[9] == "AUTO"
    # resume from match_dir only re-runs phase 3
    out2 = str(tmp_path / "out2")
    report_tiles(match_dir, "dir:" + out2, privacy=1)
    assert glob.glob(os.path.join(out2, "*", "*", "*", "*"))


def test_failed_upload_keeps_match_dir(grid_matcher, tmp_path, monkeypatch):
    """cleanup=True must not destroy match output that never shipped."""
    _write_archive(grid_matcher, str(tmp_path / "arch"), n_vehicles=2)

    class BrokenStore:
        def put(self, key, body):
            raise RuntimeError("datastore down")

    import reporter_tpu.batch.pipeline as pl

    monkeypatch.setattr(pl, "make_store", lambda spec: BrokenStore())
    trace_dir, match_dir = run_pipeline(
        grid_matcher,
        archive_spec=str(tmp_path / "arch"),
        dest_store="dir:" + str(tmp_path / "unused"),
        cleanup=True,
        valuer='lambda l: tuple(l.split("|"))',
        time_pattern=None,
        report_levels={0, 1, 2},
        transition_levels={0, 1, 2},
        privacy=1,
        source="CI",
    )
    assert trace_dir is None  # consumed by matching
    assert match_dir is not None and os.path.isdir(match_dir)  # preserved
    import shutil

    shutil.rmtree(match_dir, ignore_errors=True)


def test_privacy_cull_drops_lone_vehicle(grid_matcher, tmp_path):
    _write_archive(grid_matcher, str(tmp_path / "arch"), n_vehicles=1)
    out = str(tmp_path / "out")
    run_pipeline(
        grid_matcher,
        archive_spec=str(tmp_path / "arch"),
        dest_store="dir:" + out,
        cleanup=True,
        valuer='lambda l: tuple(l.split("|"))',
        time_pattern=None,
        report_levels={0, 1, 2},
        transition_levels={0, 1, 2},
        privacy=1000,  # nothing can meet this
        source="CI",
    )
    assert not glob.glob(os.path.join(out, "*", "*", "*", "*"))


def test_batch_end_to_end_on_dp_mesh(grid_matcher, tmp_path):
    """The batch pipeline's device micro-batches through a dp-sharded
    matcher on the virtual mesh: identical tile output to the single-device
    run (the product-path mesh, not a demo fn).  The single-device leg
    reuses the module fixture; one shared archive feeds both legs."""
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher

    mesh_matcher = SegmentMatcher(
        arrays=grid_matcher.arrays, ubodt=grid_matcher.ubodt,
        config=MatcherConfig(devices=2), backend="jax")
    _write_archive(grid_matcher, str(tmp_path / "arch"))
    kw = dict(
        archive_spec=str(tmp_path / "arch"),
        valuer='lambda l: tuple(l.split("|"))',
        time_pattern=None,
        report_levels={0, 1, 2},
        transition_levels={0, 1, 2},
        privacy=1,
        source="CI",
        quantisation=3600,
        cleanup=True,  # no resume assertions here: drop the mkdtemp dirs
    )

    outs = {}
    for name, m in (("single", grid_matcher), ("mesh", mesh_matcher)):
        out = str(tmp_path / ("out_" + name))
        run_pipeline(m, dest_store="dir:" + out, **kw)
        tiles = {}
        for f in sorted(glob.glob(os.path.join(out, "*", "*", "*", "*"))):
            rel = os.path.relpath(f, out)
            tiles[os.path.dirname(rel)] = open(f).read()
        assert tiles, "no tiles for %s" % name
        outs[name] = tiles

    assert outs["single"] == outs["mesh"], "dp mesh changed batch output"
