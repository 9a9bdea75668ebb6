"""The benchmark's map: a seeded metro city, built without the program.

A copy of the repo's realistic-city generator (``reporter_tpu/synth/
osm_city.realistic_city``) and of the OSM way splitting and OSMLR id packing
(``reporter_tpu/tiles/osm.network_from_osm``, ``tiles/hierarchy.py``,
``tiles/segment_id.py``), kept here so that no later PR can move the map
under the yardstick.  ``network(params)`` returns the network in the
server's "file" format (``RoadNetwork.to_dict``): nodes, then directed edges
with their polyline, speed, level, OSMLR segment id, internal flag and way
id.  Only numpy and the standard library are used.
"""

from __future__ import annotations

import math

import numpy as np

M_PER_DEG_LAT = 111_320.0

# highway tag -> (level, default km/h); links are internal (no OSMLR id)
HIGHWAY_CLASS = {
    "motorway": (0, 100.0), "trunk": (0, 90.0), "primary": (0, 65.0),
    "secondary": (1, 55.0), "tertiary": (1, 45.0), "unclassified": (2, 40.0),
    "residential": (2, 35.0), "living_street": (2, 15.0),
    "service": (2, 20.0), "road": (2, 40.0),
}
LINK_CLASS = {k + "_link": v for k, v in HIGHWAY_CLASS.items()
              if k in ("motorway", "trunk", "primary", "secondary", "tertiary")}

# OSMLR id layout: 3-bit level, 22-bit tile index, 21-bit segment index;
# world tile grids of 4 / 1 / 0.25 degrees per level
TILE_SIZES = {0: 4.0, 1: 1.0, 2: 0.25}


def _tile_index(level: int, lat: float, lon: float) -> int:
    size = TILE_SIZES[level]
    ncols = int(math.ceil(360.0 / size))
    row = int((lat + 90.0) / size)
    c = (lon + 180.0) / size
    col = int(c) if c >= 0.0 else int(c - 1)
    return row * ncols + col


def _segment_id(level: int, tile: int, index: int) -> int:
    return (index << 25) | (tile << 3) | level


def _classify(tags: dict):
    """(level, speed_kph, internal, oneway) or None for a non-road."""
    hw = tags.get("highway", "")
    if hw in HIGHWAY_CLASS:
        level, speed = HIGHWAY_CLASS[hw]
        internal = False
    elif hw in LINK_CLASS:
        level, speed = LINK_CLASS[hw]
        internal = True
    else:
        return None
    if tags.get("maxspeed"):
        speed = float(tags["maxspeed"])
    ow = tags.get("oneway", "")
    if ow == "yes":
        oneway = 1
    elif ow == "-1":
        oneway = -1
    elif hw in ("motorway", "motorway_link"):
        oneway = 1
    else:
        oneway = 0
    return level, speed, internal, oneway


def realistic_city(rows, cols, spacing_m, seed, origin=(37.75, -122.45)):
    """(nodes {osm_id: (lat, lon)}, ways [(way_id, refs, tags)]): a jittered,
    warped street grid with a road-class hierarchy, one-way residential
    columns, a river crossed only at bridges, dead-end blocks, two diagonal
    avenues and an orbital motorway with ramps."""
    rng = np.random.default_rng(seed)
    lat0, lon0 = origin
    m_per_deg_lon = M_PER_DEG_LAT * math.cos(math.radians(lat0))

    def to_latlon(x, y):
        return (float(round(lat0 + y / M_PER_DEG_LAT, 7)),
                float(round(lon0 + x / m_per_deg_lon, 7)))

    jit = rng.normal(0.0, spacing_m * 0.13, (rows, cols, 2))
    gx = np.zeros((rows, cols))
    gy = np.zeros((rows, cols))
    W, H = (cols - 1) * spacing_m, (rows - 1) * spacing_m
    for r in range(rows):
        for c in range(cols):
            x = c * spacing_m + jit[r, c, 0]
            y = r * spacing_m + jit[r, c, 1]
            x += 0.04 * W * math.sin(math.pi * y / max(H, 1.0))
            y += 0.025 * H * math.sin(2 * math.pi * x / max(W, 1.0))
            gx[r, c], gy[r, c] = x, y

    nodes = {}
    ways = []
    next_aux = [rows * cols + 1]

    def nid(r, c):
        i = r * cols + c + 1
        if i not in nodes:
            nodes[i] = to_latlon(gx[r, c], gy[r, c])
        return i

    def aux_node(x, y):
        nodes[next_aux[0]] = to_latlon(x, y)
        next_aux[0] += 1
        return next_aux[0] - 1

    def add_way(refs, **tags):
        ways.append((len(ways) + 1, refs, {k: str(v) for k, v in tags.items()}))

    def river_y(x):
        return H * 0.52 + H * 0.06 * math.sin(2.5 * math.pi * x / max(W, 1.0))

    def in_river(x, y):
        return abs(y - river_y(x)) < spacing_m * 0.55

    bridge_cols = set(range(4, cols - 1, max(8, cols // 12)))

    def block_way(r0, c0, r1, c1, highway, oneway=None, curve_p=0.3):
        a, b = nid(r0, c0), nid(r1, c1)
        ax, ay = gx[r0, c0], gy[r0, c0]
        bx, by = gx[r1, c1], gy[r1, c1]
        refs = [a, b]
        if rng.random() < curve_p:
            mx, my = (ax + bx) / 2, (ay + by) / 2
            dx, dy = bx - ax, by - ay
            n = math.hypot(dx, dy) or 1.0
            off = rng.normal(0, spacing_m * 0.1)
            refs = [a, aux_node(mx - dy / n * off, my + dx / n * off), b]
        tags = {"highway": highway}
        if oneway:
            tags["oneway"] = oneway
        add_way(refs, **tags)

    prim_every = max(10, rows // 8)
    sec_every = max(5, rows // 20)
    for r in range(rows):
        hw = ("primary" if r % prim_every == 0
              else "secondary" if r % sec_every == 0 else "residential")
        for c in range(cols - 1):
            mx = (gx[r, c] + gx[r, c + 1]) / 2
            my = (gy[r, c] + gy[r, c + 1]) / 2
            if in_river(mx, my):
                continue
            if hw == "residential" and rng.random() < 0.06:
                continue
            block_way(r, c, r, c + 1, hw)
    for c in range(cols):
        hw = ("primary" if c % prim_every == 0
              else "secondary" if c % sec_every == 0 else "residential")
        oneway = None
        if hw == "residential" and c % 2 == 0:
            oneway = "yes" if c % 4 == 0 else "-1"
        for r in range(rows - 1):
            mx = (gx[r, c] + gx[r + 1, c]) / 2
            my = (gy[r, c] + gy[r + 1, c]) / 2
            if in_river(mx, my):
                if c in bridge_cols:
                    block_way(r, c, r + 1, c, "secondary", curve_p=0.0)
                continue
            if hw == "residential" and rng.random() < 0.06:
                continue
            block_way(r, c, r + 1, c, hw, oneway=oneway)

    def node_xy(osm_id):
        i = osm_id - 1
        return gx[i // cols, i % cols], gy[i // cols, i % cols]

    d = min(rows, cols)
    for diag in ([nid(i, i) for i in range(d)],
                 [nid(i, cols - 1 - i) for i in range(d)]):
        run = []
        for n in diag:
            if not in_river(*node_xy(n)):
                run.append(n)
            else:
                if len(run) >= 2:
                    add_way(run, highway="tertiary", maxspeed="50")
                run = []
        if len(run) >= 2:
            add_way(run, highway="tertiary", maxspeed="50")

    ring_off = spacing_m * 2.2
    n_ring = 40
    ring = []
    for i in range(n_ring):
        t = 2 * math.pi * i / n_ring
        ring.append(aux_node(W / 2 + (W / 2 + ring_off) * math.cos(t),
                             H / 2 + (H / 2 + ring_off) * math.sin(t)))
    add_way(ring + [ring[0]], highway="motorway", maxspeed="100")
    targets = [(0, cols // 2), (rows // 2, cols - 1),
               (rows - 1, cols // 2), (rows // 2, 0)]
    for i, (rr, rc) in zip(range(0, n_ring, n_ring // 4), targets):
        add_way([ring[i], nid(rr, rc)], highway="motorway_link")
        add_way([nid(rr, rc), ring[i]], highway="motorway_link")
    return nodes, ways


def network_from_ways(nodes, ways) -> dict:
    """Split ways at intersections into directed edges, one OSMLR segment
    per directed non-internal edge, numbered per (level, tile) in way
    order; the server's "file" network format."""
    kept = []
    for wid, refs, tags in ways:
        rc = _classify(tags)
        refs = [r for r in refs if r in nodes]
        if rc is not None and len(refs) >= 2:
            kept.append((wid, refs, rc))
    use = {}
    for _wid, refs, _rc in kept:
        for i, r in enumerate(refs):
            use[r] = use.get(r, 0) + (2 if i in (0, len(refs) - 1) else 1)
    lat, lon, index = [], [], {}

    def graph_node(osm_id):
        if osm_id not in index:
            index[osm_id] = len(lat)
            lat.append(float(nodes[osm_id][0]))
            lon.append(float(nodes[osm_id][1]))
        return index[osm_id]

    counters = {}

    def next_id(level, la, lo):
        tile = _tile_index(level, la, lo)
        k = counters.get((level, tile), 0)
        counters[(level, tile)] = k + 1
        return _segment_id(level, tile, k)

    edges = []
    for wid, refs, (level, speed, internal, oneway) in kept:
        cuts = [0] + [i for i in range(1, len(refs) - 1)
                      if use.get(refs[i], 0) >= 2] + [len(refs) - 1]
        for a, b in zip(cuts, cuts[1:]):
            piece = refs[a:b + 1]
            shape = [list(nodes[r]) for r in piece]
            na, nb = graph_node(piece[0]), graph_node(piece[-1])
            for fwd in (True, False):
                if (fwd and oneway < 0) or (not fwd and oneway > 0):
                    continue
                shp = shape if fwd else shape[::-1]
                edges.append({
                    "from": na if fwd else nb, "to": nb if fwd else na,
                    "shape": shp, "speed_kph": speed, "level": level,
                    "segment_id": (None if internal
                                   else next_id(level, shp[0][0], shp[0][1])),
                    "internal": internal, "way_id": wid})
    return {"nodes": {"lat": lat, "lon": lon}, "edges": edges}


def network(params: dict) -> dict:
    """The configuration's ``city`` block -> network dict."""
    nodes, ways = realistic_city(int(params["rows"]), int(params["cols"]),
                                 float(params["spacing_m"]),
                                 int(params["seed"]))
    return network_from_ways(nodes, ways)
