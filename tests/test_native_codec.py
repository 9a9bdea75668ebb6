"""Native core: C++ tile codec and shard parser, diffed against the
pure-Python implementations of the same formats."""

import json
import os

import numpy as np
import pytest

import reporter_tpu.native as native
from reporter_tpu.native import get_lib, parse_shard_columns
from reporter_tpu.tiles import codec
from reporter_tpu.tiles.network import grid_city


@pytest.fixture(scope="module")
def lib():
    lib = get_lib()
    if lib is None:
        pytest.skip("no C++ toolchain available")
    return lib


def test_native_builds_and_reports_abi(lib):
    assert lib.rn_abi_version() == codec.VERSION


def _force_python_path(monkeypatch):
    monkeypatch.setattr(codec, "get_lib", lambda: None)


def test_tile_roundtrip_native(lib, tmp_path):
    net = grid_city(rows=4, cols=4, spacing_m=150.0, two_edge_segments=True)
    manifest = codec.save_network_tiles(net, str(tmp_path))
    assert sum(t["edges"] for t in manifest["tiles"]) == net.num_edges
    assert {t["level"] for t in manifest["tiles"]} <= {0, 1, 2}
    back = codec.load_network_tiles(str(tmp_path))
    assert back.num_nodes == net.num_nodes
    assert back.num_edges == net.num_edges
    # edge multiset equivalence (tiling reorders edges)
    def key(e):
        return (e.from_node, e.to_node, e.segment_id, e.level, round(e.speed_kph, 3))

    assert sorted(map(key, back.edges)) == sorted(map(key, net.edges))
    # shapes survive
    e0 = back.edges[0]
    assert len(e0.shape) >= 2 and isinstance(e0.shape[0][0], float)


def test_python_fallback_byte_identical(lib, tmp_path, monkeypatch):
    """The numpy fallback must produce the same bytes as the C++ writer."""
    net = grid_city(rows=3, cols=3, spacing_m=100.0)
    codec.save_network_tiles(net, str(tmp_path / "native"))
    _force_python_path(monkeypatch)
    codec.save_network_tiles(net, str(tmp_path / "python"))
    for root, _dirs, files in os.walk(str(tmp_path / "native")):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), str(tmp_path / "native"))
            a = open(os.path.join(str(tmp_path / "native"), rel), "rb").read()
            b = open(os.path.join(str(tmp_path / "python"), rel), "rb").read()
            if rel.endswith(".json"):
                assert json.loads(a) == json.loads(b)
            else:
                assert a == b, "mismatch in %s" % rel


def test_python_reads_native_tiles(lib, tmp_path, monkeypatch):
    net = grid_city(rows=3, cols=3)
    codec.save_network_tiles(net, str(tmp_path))
    _force_python_path(monkeypatch)
    back = codec.load_network_tiles(str(tmp_path))
    assert back.num_edges == net.num_edges


def test_level_filtered_load(lib, tmp_path):
    net = grid_city(rows=5, cols=5)
    codec.save_network_tiles(net, str(tmp_path))
    only_arterial = codec.load_network_tiles(str(tmp_path), levels={1})
    assert 0 < only_arterial.num_edges < net.num_edges
    assert all(e.level == 1 for e in only_arterial.edges)


def test_corrupt_tile_rejected(lib, tmp_path, monkeypatch):
    p = str(tmp_path / "bad.rptt")
    with open(p, "wb") as f:
        f.write(b"not a tile at all")
    with pytest.raises(IOError):
        codec.read_tile(p)
    # the numpy fallback must raise the same exception type
    _force_python_path(monkeypatch)
    with pytest.raises(IOError):
        codec.read_tile(p)
    # header-valid but truncated body
    import struct

    t = str(tmp_path / "trunc.rptt")
    with open(t, "wb") as f:
        f.write(struct.pack("<6I", codec.MAGIC, codec.VERSION, 100, 0, 0, 0))
    with pytest.raises(IOError):
        codec.read_tile(t)


SHARD = (
    b"veh-1,1483250740,37.75,-122.45,5\n"
    b"veh-2,1483250750,37.76,-122.44,7\n"
    b"torn-row,148325\n"
    b"veh-1,1483250760,37.77,-122.43,4\n"
)


def _python_parse(data):
    import unittest.mock as mock

    with mock.patch.object(native, "get_lib", lambda: None):
        return native.parse_shard_columns(data)


def _uuids(parsed):
    """Row i's uuid, from the (codes, distinct uuids, ...) columns."""
    return [parsed[1][c] for c in parsed[0]]


def test_parse_shard_native_vs_python(lib):
    na = parse_shard_columns(SHARD, lib=lib)
    py = _python_parse(SHARD)
    assert _uuids(na) == ["veh-1", "veh-2", "veh-1"]  # torn row skipped
    assert na[1] == ["veh-1", "veh-2"]  # each uuid once, first seen first
    assert list(na[2]) == [1483250740, 1483250750, 1483250760]
    np.testing.assert_array_equal(na[0], py[0])
    assert na[1] == py[1]
    np.testing.assert_array_equal(na[2], py[2])
    np.testing.assert_allclose(na[3], py[3])
    np.testing.assert_allclose(na[4], py[4])
    np.testing.assert_array_equal(na[5], py[5])


def test_parse_shard_crlf(lib):
    """CRLF archives must parse identically on both paths."""
    crlf = SHARD.replace(b"\n", b"\r\n")
    na = parse_shard_columns(crlf, lib=lib)
    py = _python_parse(crlf)
    assert _uuids(na) == _uuids(py) == ["veh-1", "veh-2", "veh-1"]
    np.testing.assert_array_equal(na[2], py[2])
    np.testing.assert_array_equal(na[5], py[5])


def test_parse_shard_edge_rows(lib):
    """Whitespace-only fields, leading-space uuids, and non-UTF-8 bytes must
    behave the same on both paths; distinct bytes that decode alike are one
    uuid."""
    data = (
        b"veh-1,1483250740,37.75, ,5\n"      # whitespace lon: reject
        b"  veh-2,1483250750,37.76,-122.44,7\n"  # leading ws: uuid stripped
        b"veh-\xff3,1483250760,37.77,-122.43,4\n"  # invalid utf-8 in uuid
        b"veh-\xfe3,1483250770,37.78,-122.42,4\n"  # decodes as the one above
    )
    na = parse_shard_columns(data, lib=lib)
    py = _python_parse(data)
    assert _uuids(na) == _uuids(py)
    assert _uuids(na)[0] == "veh-2"
    assert len(na[0]) == 3  # bad-lon row dropped, other three kept
    assert list(na[0]) == list(py[0]) == [0, 1, 1]
    np.testing.assert_array_equal(na[2], py[2])


def test_shard_chunked_iter(tmp_path):
    from reporter_tpu.batch.pipeline import _iter_shard_chunks

    p = str(tmp_path / "shard")
    with open(p, "wb") as f:
        f.write(SHARD)
    # tiny chunks force the carry/split logic through every boundary
    rows = []
    total_lines = 0
    for parsed in _iter_shard_chunks(p, chunk_bytes=7):
        rows.extend(zip(_uuids(parsed), parsed[2]))
        total_lines += parsed[-1]
    assert [u for u, _ in rows] == ["veh-1", "veh-2", "veh-1"]
    assert total_lines == 4


def test_service_tiles_config(lib, tmp_path):
    """The serve config 'tiles' network type loads through the codec."""
    from reporter_tpu.serve.service import load_service_config

    net = grid_city(rows=4, cols=4, spacing_m=150.0)
    codec.save_network_tiles(net, str(tmp_path / "tiles"))
    conf = {
        "network": {"type": "tiles", "path": str(tmp_path / "tiles")},
        "backend": "cpu",
    }
    cpath = str(tmp_path / "conf.json")
    with open(cpath, "w") as f:
        json.dump(conf, f)
    matcher, _ = load_service_config(cpath)
    assert matcher.arrays.num_edges == net.num_edges
