"""Self-attributing kernels: named-stage device-time attribution.

The match kernel's stages are annotated with ``jax.named_scope`` labels
(``rs.<stage>``, ops/viterbi.py / ops/hashtable.py / ops/candidates.py),
so every compiled HLO instruction carries its stage in the op-name
metadata.  This module turns a ``jax.profiler`` capture into a
per-stage device-time table with zero manual steps — the automation of
the hand-run round-4/5 attribution ritual that produced one wrong chip
claim (docs/onchip-attribution.md) and one stale headline (ROADMAP open
item 1):

  capture()        single-flight profiler window around N dispatches of a
                   runnable (obs/profiler.py's process-global lock guards
                   it against /debug/profile and concurrent captures)
  parse_*()        trace-event bucketing shared by tools/trace_analyze.py
                   and tools/kernel_breakdown.py (the duplicated logic
                   those tools carried now lives here):
                     * TPU captures: "XLA Ops" events name their scope in
                       the op metadata (and carry `source` for the legacy
                       per-file grouping);
                     * CPU captures: thunk-executor events carry only
                       `hlo_op` instruction names, bridged to stages by an
                       op->stage map read from the compiled modules'
                       metadata (register_program / the matcher registers
                       every program at its first dispatch with abstract
                       ShapeDtypeStruct args, so nothing is pinned).
  roofline_block() the rows/rep + est-gather-GB/s + hbm_frac accounting
                   the probe tools and bench.py previously each duplicated,
                   against the device kind's published HBM peak
                   (HBM_PEAK_GBS)

Surfaces: ``reporter_stage_device_seconds{stage}`` +
``reporter_attrib_age_seconds`` gauges, ``GET /debug/attrib``
(serve/service.py), a ``/statusz`` summary line, and the ``attrib`` block
in every bench.py JSON line (archived under docs/measurements/).

``REPORTER_STAGE_SCOPES=0`` disables the scope annotation at trace time
(the differential test pins annotated == unannotated bit-identically).
jax is imported lazily throughout: the module (and the gauges) stay
usable in processes that never touch the device.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import gzip
import json
import logging
import os
import re
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import metrics
from . import trace as obs_trace

log = logging.getLogger(__name__)

# canonical stage labels, in pipeline order.  The named_scope string is
# STAGE_PREFIX + label; parsers recover the label with _SCOPE_RE (taking
# the INNERMOST match — scopes nest, e.g. transition-build > ubodt-probe).
STAGE_PREFIX = "rs."
STAGES = (
    "candidate-sweep",   # grid cell gathers + projection + top-k (candidates.py)
    "emission",          # Gaussian emission scores
    "transition-build",  # edge-row gathers + [K, K] transition arithmetic
    "ubodt-probe",       # bucket-row gathers (1 wide32 / 2 cuckoo per pair)
    "select",            # in-row key match + field reduce
    "probe-compact",     # per-step distinct-node compaction, lane, expansion
    "dedup-sort",        # in-batch probe dedup: lexicographic pair sort
    "dedup-compact",     # segment-head compaction scatter
    "dedup-scatter",     # result scatter-back through segment ids
    "scan-recursion",    # sequential max-plus forward (lax.scan)
    "assoc-recursion",   # log-depth associative forward
    "backtrace",         # backpointer walk (scan or assoc composition)
    "compact-gather",    # chosen-candidate gather to the [3, B, T] result
)
UNATTRIBUTED = "(unattributed)"

_SCOPE_RE = re.compile(re.escape(STAGE_PREFIX) + r"([A-Za-z0-9_-]+)")


def scopes_enabled() -> bool:
    """Stage annotation switch, read at trace time so a fresh jit of the
    same kernel picks up a toggle (REPORTER_STAGE_SCOPES=0 disables)."""
    return os.environ.get("REPORTER_STAGE_SCOPES", "1").strip().lower() not in (
        "0", "false", "off", "no")


def stage(name: str):
    """``with stage("candidate-sweep"):`` — a jax.named_scope carrying the
    stage label into every HLO op's metadata, or a no-op context when
    annotation is disabled.  Metadata-only: the emitted ops, fusions, and
    numerics are identical either way (tests/test_attrib.py pins the
    outputs bit-identical)."""
    if not scopes_enabled():
        return contextlib.nullcontext()
    import jax

    return jax.named_scope(STAGE_PREFIX + name)


def _stage_of(*texts) -> Optional[str]:
    """Innermost stage label in any of the given strings (scopes nest, so
    the LAST match on the name-stack path is the enclosing stage)."""
    for t in texts:
        if not t:
            continue
        hits = _SCOPE_RE.findall(str(t))
        if hits:
            return hits[-1]
    return None


# ---------------------------------------------------------------------------
# program registry: the CPU op->stage bridge
#
# CPU profiler captures tag thunk-executor events with the HLO instruction
# name only (`hlo_op`), not the scope metadata.  The compiled module text
# DOES carry per-instruction op_name metadata, so each dispatched program
# registers a lazy provider (jit fn + abstract args) and the parser lowers
# them on demand into an instruction -> stage map.  Providers hold
# ShapeDtypeStructs, never live arrays — nothing is pinned.

_PROGRAMS: "Dict[str, Callable[[], Optional[str]]]" = {}
_PROGRAMS_LOCK = threading.Lock()
_MAX_PROGRAMS = 64


def _abstract_args(args) -> tuple:
    import jax

    return jax.tree_util.tree_map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if hasattr(a, "shape") and hasattr(a, "dtype") else a),
        tuple(args))


def _lower_text(fn, absargs) -> Optional[str]:
    """Compiled-module text of ``fn`` at the given abstract args; None on
    any failure (diagnostic bridge, never fatal — but logged: a silently
    empty bridge reads as '(unattributed)' downstream).

    The persistent compilation cache is BYPASSED for this compile: jax's
    cache key deliberately ignores HLO metadata, so a warm cache replays
    executables compiled before the stage scopes existed and their text
    carries no labels (measured: a bench worker with a pre-annotation
    cache mapped 0 ops).  Metadata does not influence the optimization
    pipeline, so the fresh compile's instruction names still match the
    cache-replayed executables that produced the trace events."""
    try:
        import jax

        prev = jax.config.jax_compilation_cache_dir
        try:
            if prev:
                jax.config.update("jax_compilation_cache_dir", None)
            return fn.lower(*absargs).compile().as_text()
        finally:
            if prev:
                jax.config.update("jax_compilation_cache_dir", prev)
    except Exception:  # noqa: BLE001
        log.warning("op->stage bridge: lowering a registered program "
                    "failed", exc_info=True)
        return None


def register_program(label: str, fn, args) -> None:
    """Register a jitted program for op->stage mapping.  ``args`` are the
    call's positional arguments (pytrees allowed); array leaves are
    abstracted to ShapeDtypeStructs immediately, static scalars pass
    through.  Idempotent per label; silently a no-op for callables without
    ``.lower`` (e.g. the shard_map lambda wrappers) or past the registry
    cap."""
    if not hasattr(fn, "lower"):
        return
    with _PROGRAMS_LOCK:
        if label in _PROGRAMS or len(_PROGRAMS) >= _MAX_PROGRAMS:
            return
    absargs = _abstract_args(args)
    cache: list = []

    def provider() -> Optional[str]:
        if not cache:
            cache.append(_lower_text(fn, absargs))
        return cache[0]

    with _PROGRAMS_LOCK:
        _PROGRAMS.setdefault(label, provider)


def registered_program_labels() -> List[str]:
    with _PROGRAMS_LOCK:
        return sorted(_PROGRAMS)


def _registry_hlo_texts() -> List[str]:
    with _PROGRAMS_LOCK:
        providers = list(_PROGRAMS.values())
    texts = []
    for prov in providers:
        t = prov()
        if t:
            texts.append(t)
    return texts


_HLO_MODULE_RE = re.compile(r"HloModule\s+([\w.\-]+)")
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s.*metadata=\{([^}]*)\}")


def op_stage_map_from_hlo(texts: Sequence[str]) -> Dict[object, str]:
    """(hlo_module, instr) and bare-instr keys -> stage label, from the
    op_name metadata of compiled HLO module texts.  Fusions carry their
    root op's path; an instruction whose metadata names no stage is
    simply absent (parsers fall back to UNATTRIBUTED)."""
    out: Dict[object, str] = {}
    for txt in texts:
        m = _HLO_MODULE_RE.search(txt or "")
        mod = m.group(1) if m else ""
        for line in (txt or "").splitlines():
            im = _HLO_INSTR_RE.match(line)
            if not im:
                continue
            st = _stage_of(im.group(2))
            if st:
                out[(mod, im.group(1))] = st
                out[im.group(1)] = st
    return out


def build_op_stage_map(programs=None) -> Dict[object, str]:
    """Map from explicit ``programs`` ([(fn, args), ...]) or, when None,
    from every registered program.  Explicit programs stay local — they
    neither enter nor read the global registry, so a tool profiling one
    program maps exactly that program."""
    if programs is None:
        return op_stage_map_from_hlo(_registry_hlo_texts())
    texts = []
    for fn, args in programs:
        if not hasattr(fn, "lower"):
            continue
        txt = _lower_text(fn, _abstract_args(args))
        if txt:
            texts.append(txt)
    return op_stage_map_from_hlo(texts)


# ---------------------------------------------------------------------------
# trace-event parsing (the one home for the bucketing trace_analyze.py and
# kernel_breakdown.py used to duplicate)


def parse_trace_events(events, op_stage_map: Optional[dict] = None) -> dict:
    """Chrome-trace event list -> attribution dict.

    TPU captures: device time is the "XLA Ops" thread of every TPU
    process (one per chip); the stage comes from the scope label in the
    event name or any args value (long_name / tf_op / op_name), with the
    op_stage_map as a fallback.  CPU captures: per-op thunk-executor
    events (``hlo_op`` arg) resolved through the op_stage_map; summed op
    durations can exceed wall clock when the executor runs ops in
    parallel — fractions, not wall time, are the signal.

    Also keeps the legacy per-module / per-file / per-line groupings
    (TPU traces attach ``source`` to the first occurrence of each op
    name) so tools/trace_analyze.py's output format survives."""
    dev_pids = set()
    tids = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if (e.get("name") == "process_name"
                and "TPU" in str((e.get("args") or {}).get("name", ""))):
            dev_pids.add(e["pid"])
        if e.get("name") == "thread_name":
            tids[(e.get("pid"), e.get("tid"))] = (e.get("args") or {}).get("name", "")
    platform = "tpu" if dev_pids else "cpu"

    stages: Dict[str, float] = collections.defaultdict(float)
    by_file: Dict[str, float] = collections.defaultdict(float)
    by_line: Dict[str, float] = collections.defaultdict(float)
    by_module: Dict[str, float] = collections.defaultdict(float)
    name_src: Dict[str, str] = {}
    name_stage: Dict[str, str] = {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        name = str(e.get("name", ""))
        if platform == "tpu":
            if e.get("pid") not in dev_pids:
                continue
            tname = tids.get((e.get("pid"), e.get("tid")), "")
            dur = e.get("dur", 0) / 1e3  # us -> ms
            if tname == "XLA Modules":
                by_module[name.split("(")[0]] += dur
                continue
            if tname != "XLA Ops":
                continue
            mod = args.get("hlo_module")
            op = args.get("hlo_op") or name
        else:
            if "hlo_op" not in args:
                continue
            dur = e.get("dur", 0) / 1e3
            mod = args.get("hlo_module", "")
            op = args.get("hlo_op")
            by_module[mod] += dur
        total += dur
        # args are attached to the first occurrence of each op name on TPU
        # traces; remember both the source and the resolved stage
        if "source" in args:
            name_src[name] = args["source"]
        st = _stage_of(name, *args.values())
        if st is None and op_stage_map:
            st = (op_stage_map.get((mod, op))
                  or op_stage_map.get(op))
        if st is None:
            st = name_stage.get(name)
        else:
            name_stage[name] = st
        stages[st or UNATTRIBUTED] += dur
        src = name_src.get(name, "")
        fname = src.rsplit("/", 1)[-1].split(":")[0] if src else "(no source)"
        by_file[fname] += dur
        if src:
            by_line[src.replace("/root/repo/", "")] += dur

    def _sorted(d, keep=None, floor=0.0):
        items = sorted(d.items(), key=lambda kv: -kv[1])
        if keep:
            items = items[:keep]
        return {k: round(v, 3) for k, v in items if v > floor}

    return {
        "platform": platform,
        "devices": len(dev_pids),
        "device_total_ms": round(total, 3),
        "stages_ms": _sorted(stages),
        "by_module_ms": _sorted(by_module, floor=0.05),
        "by_file_ms": _sorted(by_file),
        "top_lines_ms": _sorted(by_line, keep=14),
    }


def parse_trace_file(path: str, op_stage_map: Optional[dict] = None) -> dict:
    """One ``*.trace.json[.gz]`` chrome trace -> attribution dict."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path) as f:
        tr = json.load(f)
    out = parse_trace_events(tr.get("traceEvents", []), op_stage_map)
    out["path"] = path
    return out


def trace_files(trace_dir: str) -> List[str]:
    return sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                    recursive=True))


def parse_trace_dir(trace_dir: str, op_stage_map: Optional[dict] = None) -> dict:
    """Parse every chrome trace under a jax.profiler output dir and merge
    (a mesh capture writes one trace per host)."""
    paths = trace_files(trace_dir)
    if not paths:
        raise FileNotFoundError("no *.trace.json[.gz] under %s" % trace_dir)
    merged: Optional[dict] = None
    for p in paths:
        one = parse_trace_file(p, op_stage_map)
        if merged is None:
            merged = one
            continue
        merged["devices"] += one["devices"]
        merged["device_total_ms"] = round(
            merged["device_total_ms"] + one["device_total_ms"], 3)
        for k in ("stages_ms", "by_module_ms", "by_file_ms", "top_lines_ms"):
            for name, ms in one[k].items():
                merged[k][name] = round(merged[k].get(name, 0.0) + ms, 3)
        if one["platform"] == "tpu":
            merged["platform"] = "tpu"
    merged["path"] = trace_dir
    return merged


# ---------------------------------------------------------------------------
# roofline / row accounting — the ONE home for the cost model bench.py and
# tools/kernel_stage_probe.py previously each carried


def dedup_budget(n_pairs: int) -> int:
    """Static compacted-unique capacity of the in-batch probe dedup for a
    dispatch of ``n_pairs`` probe pairs (ops/hashtable._lookup_dedup's
    budget, exactly)."""
    from ..ops.hashtable import _DEDUP_CAP_RATIO, _DEDUP_MIN_PAIRS

    return max(_DEDUP_MIN_PAIRS // 2, n_pairs // _DEDUP_CAP_RATIO)


def executed_rows(n_pairs: int, max_probes: int, dedup: bool = False) -> int:
    """Executed bucket-row gathers for a dispatch: the row-count-bound cost
    model (docs/gather-experiments.md — rows/s is flat across row widths).
    ``max_probes`` is the table layout's architectural probe count (2
    cuckoo / 1 wide32); with dedup the deduped path gathers its static
    budget instead of every occurrence."""
    return max_probes * (dedup_budget(n_pairs) if dedup else n_pairs)


# Published peak HBM bandwidth by ``jax.Device.device_kind``, in GB/s.
# Source: Google Cloud documentation, "TPU v5e" (16 GB of HBM at
# 819 GB/s per chip).  A kind missing here is an error, never a default:
# a roofline share against a guessed peak is not a number.
HBM_PEAK_GBS = {
    "TPU v5 lite": 819.0,
}


def hbm_peak_gbs(device_kind: str) -> float:
    """The published HBM peak of ``device_kind``; ValueError when the
    table has none."""
    try:
        return HBM_PEAK_GBS[device_kind]
    except KeyError:
        raise ValueError(
            "no published HBM peak for device kind %r (add it to "
            "obs/attrib.HBM_PEAK_GBS with its source)" % (device_kind,)
        ) from None


def roofline_block(n_traces: int, T: int, k: int, secs: float, *,
                   bucket_entries: int, max_probes: int, grid_cap: int,
                   hbm_gbs: float, dedup: bool = False) -> dict:
    """Estimated useful gather bandwidth for one cohort's kernel rep and
    its fraction of nominal HBM (application-level bytes).  Two dominant
    gather streams per trace: the UBODT transition probes (max_probes
    bucket rows per [T-1, K, K] entry) and the candidate sweep (4 quadrant
    cell rows of cap 32-byte records per point — the 2x2 sweep,
    ops/candidates.py).  The byte model ignores dedup (with dedup on it is
    an upper bound on probe traffic); ``rows_per_rep`` reports the
    EXECUTED dedup-aware row count alongside."""
    from ..tiles.ubodt import ROW_W

    pairs_per_trace = (T - 1) * k * k
    row_bytes = bucket_entries * ROW_W * 4
    ubodt_b = pairs_per_trace * max_probes * row_bytes
    cand_b = T * 4 * grid_cap * 32
    gbs = (ubodt_b + cand_b) * n_traces / max(secs, 1e-9) / 1e9
    return {
        "est_gather_gb_per_s": round(gbs, 2),
        "hbm_frac": round(gbs / hbm_gbs, 4),
        "rows_per_rep": executed_rows(
            n_traces * pairs_per_trace, max_probes, dedup),
    }


# ---------------------------------------------------------------------------
# capture orchestration + the live store


# -- host-stage attribution (docs/performance.md "The columnar host data
# plane"): wall seconds the GIL-bound host spends per pipeline stage,
# counted where each stage runs (service request decode -> matcher pack
# -> device dispatch -> result fetch -> host association -> report() ->
# tile rows -> service response encode; the batch pipeline adds the shard
# read).  The device side has named_scope attribution; this is its host
# mirror, on the profiler's clock too: every stage is also a
# ``reporter.<stage>`` host span in a jax.profiler capture, so an idle
# gap in the device trace is named by the host work under it.
HOST_STAGES = ("parse", "pack", "dispatch", "fetch", "associate", "report",
               "tile", "read", "serialize")
HOST_SPAN_PREFIX = "reporter."
C_HOST_STAGE = metrics.counter(
    "reporter_host_stage_seconds_total",
    "Wall seconds of host pipeline work by stage (parse = request-body "
    "decode, pack = batch packing into padded device arrays, dispatch = "
    "device program enqueue, fetch = blocking wait for a batch's device "
    "result and its copy to the host, associate = host segment "
    "association of fetched results, report = report() rendering, tile = "
    "batch tile-row fan-out and file writes, read = batch shard-file "
    "parse and window split, serialize = response encode; GET "
    "/debug/attrib reports the split)",
    ("stage",))
# one child per stage, made once: the series exist from the start and a
# stage's increment skips the label lookup
_HOST_STAGE_CHILD = {s: C_HOST_STAGE.labels(s) for s in HOST_STAGES}


def _host_annotation(stage: str, span, meta: dict):
    """The ``reporter.<stage>`` TraceAnnotation, or None where no caller
    has imported jax."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    if meta.get("trace_id") is None:
        tid = (span.trace_id if span is not None
               else obs_trace.current_trace_id())
        if tid is None:
            meta.pop("trace_id", None)
        else:
            meta["trace_id"] = tid
    return profiler.TraceAnnotation(HOST_SPAN_PREFIX + stage, **meta)


class host_stage:
    """``with host_stage("fetch", span, n=len(batch)):`` — time one host
    stage of one request or micro-batch (never one point or window).

    On exit the block's wall seconds go to
    ``reporter_host_stage_seconds_total{stage}``.  While it is open, a
    ``jax.profiler.TraceAnnotation("reporter.<stage>", trace_id=..., **meta)``
    marks it on the profiler's host timeline, so a capture shows which host
    stage sits under each device idle gap.  ``trace_id`` is ``meta``'s, else
    ``span``'s, else the bound span's (obs/trace.py); a batch passes its
    lead span and ``n``, its trace count.  jax is used only when a caller
    has already imported it, so obs stays importable without jax; with no
    capture recording, the annotation is one TraceMe enter/exit."""

    __slots__ = ("_child", "_ann", "_t0")

    def __init__(self, stage: str, span=None, **meta):
        self._child = _HOST_STAGE_CHILD[stage]
        self._ann = _host_annotation(stage, span, meta)

    def __enter__(self) -> "host_stage":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._child.inc(time.perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def host_span(name: str, span=None, **meta):
    """A ``reporter.<name>`` profiler span with no counter, for waiting
    that another metric already counts (the micro-batcher's fill window)."""
    return _host_annotation(name, span, meta) or contextlib.nullcontext()


def host_snapshot() -> Dict[str, float]:
    """Cumulative seconds per host stage, read from the counter."""
    return {lv[0]: child.value for lv, child in C_HOST_STAGE._items()}


def host_summary(since: Optional[Dict[str, float]] = None) -> dict:
    """The host-stage split: cumulative (or since a snapshot) seconds per
    stage plus each stage's share of the host total.  ``fetch`` is a wait
    on the device, not host work, so the total and the split leave it
    out."""
    now = host_snapshot()
    if since:
        now = {k: max(0.0, v - since.get(k, 0.0)) for k, v in now.items()}
    work = {k: v for k, v in now.items() if k != "fetch"}
    total = sum(work.values())
    return {
        "stages_s": {k: round(v, 6) for k, v in now.items()},
        "total_s": round(total, 6),
        "split": {k: (round(v / total, 4) if total > 0 else 0.0)
                  for k, v in work.items()},
    }


def host_frac(host_s: float, device_s: float) -> Optional[float]:
    """host / (host + device) over ONE window — the bench artifact's
    headline host share.  None when the window carries no work."""
    denom = host_s + device_s
    return round(host_s / denom, 4) if denom > 0 else None


G_STAGE_S = metrics.gauge(
    "reporter_stage_device_seconds",
    "Device seconds per named kernel stage in the last parsed attribution "
    "capture (jax.named_scope labels; GET /debug/attrib)",
    ("stage",))
G_ATTRIB_AGE = metrics.gauge(
    "reporter_attrib_age_seconds",
    "Seconds since the last parsed attribution capture (-1 until one runs)")

_LAST: Optional[dict] = None
_LAST_LOCK = threading.Lock()


def _update_age() -> None:
    with _LAST_LOCK:
        ts = _LAST.get("captured_unix") if _LAST else None
    G_ATTRIB_AGE.set(round(time.time() - ts, 3) if ts else -1.0)


metrics.REGISTRY.register_collect(_update_age)


def store_result(result: dict) -> None:
    """Publish a parsed capture: the /debug/attrib 'last' slot and the
    stage gauges (previous capture's stages zeroed so a stage that
    vanished does not linger)."""
    global _LAST
    with _LAST_LOCK:
        prev, _LAST = _LAST, result
    for name in (prev or {}).get("stages_ms", {}):
        G_STAGE_S.labels(name).set(0.0)
    for name, ms in result.get("stages_ms", {}).items():
        G_STAGE_S.labels(name).set(ms / 1e3)
    _update_age()


def last() -> Optional[dict]:
    with _LAST_LOCK:
        return dict(_LAST) if _LAST else None


def capture(run_fn: Callable[[], object], reps: int = 3,
            out_dir: Optional[str] = None,
            programs: Optional[Sequence[Tuple[object, tuple]]] = None,
            trace_id: Optional[str] = None,
            store: bool = True, warm: bool = True) -> dict:
    """The programmatic capture window: profile ``reps`` calls of
    ``run_fn`` (each must block on its device result — fetch, don't just
    dispatch), parse the emitted trace events into the per-stage table,
    and publish it.  Single-flight via obs/profiler's process-global lock:
    a concurrent capture (here or /debug/profile) raises ProfilerBusy
    carrying the in-flight capture's trace_id.

    ``warm`` runs one un-profiled call first: a compile INSIDE the window
    floods the trace's event cap with host tracing (measured: 1M events,
    every device op dropped) besides polluting the timings.

    On a CPU capture whose events carry no scope labels, the op->stage
    map is built from ``programs`` ([(jit_fn, args), ...]) or, when None,
    from every program the matcher registered at first dispatch — that
    lowers+compiles each one once per process, a diagnostic-path cost."""
    from . import profiler

    reps = max(1, int(reps))
    if warm:
        run_fn()
    host0 = host_snapshot()
    with profiler.session("attrib", trace_id=trace_id, out_dir=out_dir) as d:
        t0 = time.time()
        for _ in range(reps):
            run_fn()
        wall = time.time() - t0
    host_win = host_summary(since=host0)
    result = parse_trace_dir(d)
    if (result["platform"] == "cpu"
            and set(result["stages_ms"]) <= {UNATTRIBUTED}):
        m = build_op_stage_map(programs)
        if m:
            r2 = parse_trace_dir(d, m)
            r2["path"] = result["path"]
            result = r2
        if set(result["stages_ms"]) <= {UNATTRIBUTED}:
            log.warning(
                "attribution capture resolved no stages (cpu bridge: %d "
                "map entries from %s) — table is all-(unattributed)",
                len(m), "explicit programs" if programs is not None
                else "%d registered programs" % len(registered_program_labels()))
    result.update({
        "captured_unix": round(time.time(), 3),
        "captured": time.strftime("%Y-%m-%d"),
        "reps": reps,
        "wall_s": round(wall, 4),
        "trace_dir": d,
        # the host half of the same window: stage split + host share of
        # (host + device) — the split /debug/attrib and bench report
        # alongside the kernel table (docs/bench-schema.md host_frac)
        "host_stages_s": host_win["stages_s"],
        "host_frac": host_frac(
            host_win["total_s"],
            float(result.get("device_total_ms") or 0.0) / 1e3),
    })
    if store:
        store_result(result)
    return result


def capture_matcher(matcher, reps: int = 3, length: Optional[int] = None,
                    trace_id: Optional[str] = None) -> dict:
    """Capture ``reps`` live dispatches of a SegmentMatcher (the
    /debug/attrib trigger): dummy traces through the REAL dispatch path,
    so the profiled programs are exactly the serving ones."""
    if length is None:
        length = int(matcher.cfg.length_buckets[0]) if matcher.cfg.length_buckets else 64
    traces = matcher.dummy_traces(max(2, length), 1)
    return capture(lambda: matcher.match_many(traces), reps=reps,
                   trace_id=trace_id)


def summary() -> dict:
    """The /statusz line: capture age + platform + headline stage, so a
    stale attribution (or a CPU-only one) is visible at a glance next to
    the serving metrics."""
    res = last()
    out: dict = {"captured": bool(res), "host": host_summary()}
    if res:
        out.update({
            "age_s": round(time.time() - res.get("captured_unix", 0), 1),
            "platform": res.get("platform"),
            "device_total_ms": res.get("device_total_ms"),
        })
        if res.get("host_frac") is not None:
            out["host_frac"] = res["host_frac"]
        stages = {k: v for k, v in res.get("stages_ms", {}).items()
                  if k != UNATTRIBUTED}
        if stages:
            top = max(stages.items(), key=lambda kv: kv[1])
            out["top_stage"] = {"stage": top[0], "ms": top[1]}
    return out


# ---------------------------------------------------------------------------
# measurement provenance + archive


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def archive(block: dict, platform: str, repo: Optional[str] = None) -> Optional[str]:
    """Write an attribution artifact under docs/measurements/ as
    ``attrib_<platform>_<date>[_<replica>].json`` and return its
    repo-relative path.  The replica tag ($REPORTER_REPLICA_ID, when set)
    keeps N fleet replicas sharing a checkout from clobbering one
    another's same-day archives.  Returns
    None when the measurements bank is absent (installed-package
    deployments)."""
    repo = repo or repo_root()
    d = os.path.join(repo, "docs", "measurements")
    if not os.path.isdir(d):
        return None
    rid = re.sub(r"[^A-Za-z0-9._-]", "_",
                 os.environ.get("REPORTER_REPLICA_ID", "").strip())
    name = "attrib_%s_%s%s.json" % (platform, time.strftime("%Y-%m-%d"),
                                    "_" + rid if rid else "")
    path = os.path.join(d, name)
    try:
        with open(path, "w") as f:
            json.dump(dict(block, platform=platform), f, indent=1, sort_keys=True)
    except OSError:
        return None
    return os.path.relpath(path, repo)
