"""Library-ified per-op profiler for the bench workloads.

Extracted from scripts/profile_trace.py (which is now a thin CLI over
this module) so ANY (N, r, config) shape can be profiled and the result
consumed as data — the round-5 BASELINE.md table was hand-transcribed
from script stdout; the round-6 ask ("per-op profile of the 12.5k
shard") lands as a :class:`ProfileTable`.

Capture runs the EXACT bench workload (perf.sweep.build_bench) under
``jax.profiler.trace`` so op attribution maps 1:1 onto what
BENCH_r*.json measures. Three summarization backends, tried in order:

  1. ``xprof.convert`` hlo_stats — the driver image's converter (what
     produced the round-5 table);
  2. ``tensorboard_plugin_profile.convert`` hlo_stats — same tool data,
     older packaging;
  3. direct ``*.xplane.pb`` parsing — no converter at all: walks the
     XSpace event trees (per-line interval nesting -> self times) and
     aggregates per-op self time. This is the backend that works on the
     bare-CPU test image, and it is what makes ``parse_xspace_bytes``
     unit-testable with a synthetic XSpace.

The backends see the same trace; they differ only in who does the
self-time bookkeeping. ``ProfileTable.backend`` records which ran.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import defaultdict

from .stages import UNSCOPED


@dataclasses.dataclass
class OpRow:
    """One HLO op's attributed cost in the profiled segment."""

    name: str
    category: str
    self_us_per_round: float
    occurrences: int = 0
    source: str = ""
    text: str = ""
    #: the engine stage of the op (perf/stages.py: the innermost ``gs.*``
    #: scope of its instruction in the compiled window); "" without a map
    stage: str = ""


@dataclasses.dataclass
class ProfileTable:
    """Attributed per-op table for one profiled workload segment."""

    rows: list            # [OpRow], sorted by self time desc
    total_us_per_round: float
    rounds: int
    backend: str
    fingerprint: dict | None = None

    @property
    def by_category(self) -> dict:
        out = defaultdict(float)
        for r in self.rows:
            out[r.category] += r.self_us_per_round
        return dict(out)

    @property
    def by_stage(self) -> dict:
        """Self time per round by engine stage; empty when the table was
        made without a stage map."""
        out = defaultdict(float)
        for r in self.rows:
            if r.stage:
                out[r.stage] += r.self_us_per_round
        return dict(out)

    @property
    def n_kernels_per_round(self) -> float:
        """Executed kernels (op occurrences) per simulated round — the
        launch-overhead metric the round-7 stacked-plane work optimizes
        (the 12.5k shard is fusion-COUNT-bound, not bandwidth-bound:
        docs/PERF.md round-6/7 tables). xplane backend: every executed
        thunk event; converter backends: row occurrences (same trace,
        same trend)."""
        return sum(r.occurrences for r in self.rows) / max(self.rounds, 1)

    @property
    def kernels_by_category(self) -> dict:
        """Per-round executed-kernel counts by op category, largest
        first (fusion / copy / call / reduce / ...)."""
        out = defaultdict(int)
        for r in self.rows:
            out[r.category] += r.occurrences
        rd = max(self.rounds, 1)
        return {
            k: round(v / rd, 2)
            for k, v in sorted(out.items(), key=lambda x: -x[1])
        }

    def top(self, n: int = 30) -> list:
        return self.rows[:n]


# ---------------------------------------------------------------------------
# backend 3: direct xplane parsing (no converter dependency)


def _import_xplane_pb2():
    """The XSpace proto ships under several package roots depending on
    which profiler wheel is installed; take the first importable."""
    import importlib

    for mod in (
        "xprof.protobuf.xplane_pb2",
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",
        "tsl.profiler.protobuf.xplane_pb2",
        "tensorboard_plugin_profile.protobuf.xplane_pb2",
    ):
        try:
            return importlib.import_module(mod)
        except ImportError:
            continue
    return None


def _self_times(events):
    """(start_ps, dur_ps, key) intervals -> [(key, self_ps)] with each
    interval's children (strictly nested, same line) subtracted."""
    evs = sorted(events, key=lambda t: (t[0], -t[1]))
    out = []
    stack = []  # [start, end, key, child_sum]

    def finish():
        s, e, key, child = stack.pop()
        out.append((key, (e - s) - child))
        if stack:
            stack[-1][3] += e - s

    for s, d, key in evs:
        while stack and stack[-1][1] <= s:
            finish()
        stack.append([s, s + d, key, 0])
    while stack:
        finish()
    return out


_CATEGORY_RE = re.compile(r"^[a-zA-Z-]+")


def _category_of(name: str, explicit: str | None) -> str:
    """Fallback category when the plane carries no ``hlo_category`` stat
    (XLA:CPU): fused computations are named ``<roots>_fusion.N[.clone]``
    — bucket them all as "fusion" (what the TPU hlo_stats tool reports);
    plain ops keep their leading mnemonic."""
    if explicit:
        return explicit
    if "fusion" in name:
        return "fusion"
    m = _CATEGORY_RE.match(name)
    return m.group(0) if m else name


def _stage(stage_of: dict | None, name: str) -> str:
    return "" if stage_of is None else stage_of.get(name, UNSCOPED)


def parse_xspace_bytes(blobs, rounds: int,
                       stage_of: dict | None = None) -> ProfileTable:
    """Aggregate per-op self times from serialized XSpace protos.
    ``stage_of`` (``perf.stages``: instruction name -> stage) fills each
    row's ``stage``.

    Takes HLO-op events from two plane shapes: device planes (plane name
    contains "device"/"TPU" — TPU runs), and host planes' executor lines
    whose events carry an ``hlo_op`` stat (XLA:CPU runs). Python/trace
    bookkeeping lines carry no hlo stats and are skipped."""
    xplane_pb2 = _import_xplane_pb2()
    if xplane_pb2 is None:
        raise RuntimeError(
            "no xplane proto module importable (xprof, tensorflow.tsl, "
            "tsl, or tensorboard_plugin_profile)"
        )
    agg = {}  # name -> [self_ps, count, category, source]
    for blob in blobs:
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(blob)
        for plane in xs.planes:
            is_device = ("device" in plane.name.lower()
                         or "tpu" in plane.name.lower())
            emeta = plane.event_metadata
            smeta = plane.stat_metadata
            for line in plane.lines:
                intervals = []
                info = {}
                for ev in line.events:
                    stats = {}
                    for st in ev.stats:
                        sname = smeta[st.metadata_id].name
                        if st.str_value:
                            stats[sname] = st.str_value
                        elif st.ref_value:
                            stats[sname] = smeta[st.ref_value].name
                    name = stats.get("hlo_op") or emeta[ev.metadata_id].name
                    if "hlo_op" not in stats and not (
                            is_device and line.name.startswith("XLA")):
                        continue
                    if ev.duration_ps <= 0:
                        continue
                    intervals.append((ev.offset_ps, ev.duration_ps, name))
                    if name not in info:
                        info[name] = (
                            stats.get("hlo_category"),
                            stats.get("source") or stats.get("source_info", ""),
                        )
                for name, self_ps in _self_times(intervals):
                    cat, src = info.get(name, (None, ""))
                    row = agg.setdefault(
                        name, [0, 0, _category_of(name, cat), src])
                    row[0] += self_ps
                    row[1] += 1
    rows = [
        OpRow(name=k, category=v[2],
              self_us_per_round=v[0] / 1e6 / max(rounds, 1),
              occurrences=v[1], source=v[3], stage=_stage(stage_of, k))
        for k, v in agg.items()
    ]
    rows.sort(key=lambda r: -r.self_us_per_round)
    return ProfileTable(
        rows=rows,
        total_us_per_round=sum(r.self_us_per_round for r in rows),
        rounds=rounds,
        backend="xplane",
    )


# ---------------------------------------------------------------------------
# compiled-HLO kernel census (no execution — the perf-smoke gate's input)

#: top-level instructions that never launch a kernel
_NON_KERNEL_OPS = frozenset(
    {"parameter", "get-tuple-element", "constant", "tuple", "bitcast"}
)


def hlo_kernel_census(hlo_text: str) -> dict:
    """Thunk-level kernel counts of a compiled HLO module, by op.

    Counts instructions of every computation EXCEPT fusion bodies
    (``fused_computation*`` — their ops run inside the enclosing fusion
    kernel) and reduction/scatter combiner regions (``region*``), and
    skips the no-kernel bookkeeping ops (parameters, GTEs, constants,
    tuples, bitcasts). The result approximates the executed launch count
    of one invocation on XLA:CPU — the number ``make perf-smoke``'s
    kernel-count gate pins (perf/regress.py), with the per-op breakdown
    for diagnosis. Returns {"total": int, "by_op": {op: count}}."""
    import collections

    counts = collections.Counter()
    for comp in re.split(r"\n(?=%|ENTRY)", hlo_text):
        header = comp.split("\n", 1)[0]
        m = re.match(r"(ENTRY )?%?([\w.\-]+)", header)
        if (m is None or "fused_computation" in m.group(2)
                or m.group(2).startswith("region")):
            continue
        # result type is a single token OR a tuple "(s32[], u32[2]{0})"
        # — while loops and multi-output fusions use the tuple form
        counts.update(
            re.findall(r"= (?:\([^)]*\)|\S+?) ([\w\-]+)\(", comp)
        )
    by_op = {
        k: v for k, v in counts.most_common() if k not in _NON_KERNEL_OPS
    }
    return {"total": sum(by_op.values()), "by_op": by_op}


#: the PRNG impl every committed kernel-census baseline was measured
#: under (the bench PRNG the gate scripts pin). The census is
#: PRNG-impl-DEPENDENT: the chaos-off PERF_SMOKE shape compiles to 393
#: kernels under unsafe_rbg but 376 under the ambient threefry default
#: — a "376 != 393" reading under the wrong impl is a measurement
#: error, not a regression.
GATE_PRNG_IMPL = "unsafe_rbg"
_CENSUS_PRNG_NOTE = (
    "the compiled kernel census is PRNG-impl-dependent (chaos-off "
    "PERF_SMOKE shape: 393 kernels under unsafe_rbg, 376 under "
    "threefry), so every committed baseline is defined under the bench "
    "PRNG"
)


def require_gate_prng() -> None:
    """Hard-fail a census taken under the wrong PRNG impl.

    Every HLO kernel-census gate (perf-smoke, chaos-smoke's
    elision-when-off equality, telemetry-smoke, oracle-smoke) pins
    ``unsafe_rbg`` in its main(); calling the census helper from an
    ambient-PRNG context (a pytest session, a REPL) used to produce a
    bare '376 != committed 393' mismatch that reads as an image
    regression. Raise the informative error instead."""
    import jax

    impl = str(jax.config.jax_default_prng_impl)
    if impl != GATE_PRNG_IMPL:
        raise RuntimeError(
            f"kernel census requested under PRNG impl {impl!r}, but "
            f"{_CENSUS_PRNG_NOTE}. Pin it first — "
            f"jax.config.update('jax_default_prng_impl', "
            f"'{GATE_PRNG_IMPL}') — or run the gate script, which does."
        )


#: the measured-on-THIS-image census baselines (gitignored, lives in
#: the repo-local .jax_cache dir next to the compiled executables —
#: both are image-scoped artifacts)
ONIMAGE_CENSUS_BASENAME = "CENSUS_ONIMAGE.json"


def on_image_census_baseline(census: dict, variant: str = "default",
                             root: str | None = None,
                             update: bool = False) -> dict:
    """Seed-or-read the on-image census baseline for one shape/variant.

    The compiled-HLO kernel census is IMAGE-dependent (XLA version,
    fusion heuristics): PR 8 recorded this gate reading 324 on an image
    whose committed PERF_SMOKE baseline said 393 — on the seed tree
    too, so the mismatch was a container change, not a regression. The
    census gates therefore compare DIFF-NEUTRALLY: the first gate run
    on an image measures the census and seeds this baseline
    (``.jax_cache/CENSUS_ONIMAGE.json``, keyed by jax version +
    platform + shape); later runs on the same image fail only when the
    census moves against that on-image value — i.e. when THIS tree's
    code changed it. The committed baseline stays as an informational
    pin (gates print the comparison; they no longer fail on it).

    Returns ``{"total": int, "seeded": bool, "path": str}`` — ``seeded``
    True when this call wrote the entry (nothing to compare yet).
    ``update=True`` force-rewrites the entry from the current
    measurement — the *_SMOKE_UPDATE=1 rebaseline path, so a deliberate
    census change is accepted the same way a committed-rate change is."""
    import jax

    from .artifacts import _repo_root

    path = os.path.join(root or _repo_root(), ".jax_cache",
                        ONIMAGE_CENSUS_BASENAME)
    stamp = {"jax": jax.__version__, "platform": jax.default_backend()}
    key = (f"{variant}_n{census['n_peers']}_r{census['rounds_per_phase']}")
    doc = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = None
    if not isinstance(doc, dict) or doc.get("stamp") != stamp:
        # new image (or corrupted file): every entry is stale
        doc = {"stamp": stamp, "note": (
            "measured-on-this-image compiled-HLO census baselines "
            "(perf.profile.on_image_census_baseline); delete to reseed"),
            "entries": {}}
    entry = doc["entries"].get(key)
    if entry is None or update:
        doc["entries"][key] = {"total": int(census["total"])}
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        return {"total": int(census["total"]), "seeded": True, "path": path}
    return {"total": int(entry["total"]), "seeded": False, "path": path}


def compiled_phase_kernel_count(n_peers: int, rounds_per_phase: int,
                                config: str = "default",
                                msg_slots: int = 64,
                                telemetry=None) -> dict:
    """Compile the bench phase step at (n_peers, r) on the current
    platform and census its kernels (hlo_kernel_census). Adds
    ``per_round`` — the gate's headline number. ``telemetry`` (a
    telemetry.TelemetryConfig) censuses the TELEMETRY-ON build instead
    (live counters + panel recorder — the `make telemetry-smoke`
    variant; None is the committed PERF_SMOKE/chaos-smoke build).

    Refuses to run under any PRNG impl other than the gate's
    (:func:`require_gate_prng`) — a census taken under ambient threefry
    is incomparable to every committed baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .sweep import PUBS_PER_ROUND, build_bench

    require_gate_prng()

    r = max(int(rounds_per_phase), 1)
    st, step, _, _ = build_bench(
        n_peers, msg_slots, config=config, heartbeat_every=max(r, 1),
        rounds_per_phase=r, telemetry=telemetry,
        count_events=(True if telemetry is not None else None),
    )
    shape = (r, PUBS_PER_ROUND) if r > 1 else (PUBS_PER_ROUND,)
    po = jnp.asarray(np.full(shape, -1, np.int32))
    pt = jnp.asarray(np.zeros(shape, np.int32))
    pv = jnp.asarray(np.ones(shape, bool))
    if r > 1:
        lowered = step.lower(st, po, pt, pv, do_heartbeat=True)
    else:
        lowered = step.lower(st, po, pt, pv)
    census = hlo_kernel_census(lowered.compile().as_text())
    census["per_round"] = round(census["total"] / r, 2)
    census["n_peers"] = int(n_peers)
    census["rounds_per_phase"] = r
    census["telemetry"] = telemetry is not None
    return census


# ---------------------------------------------------------------------------
# backends 1-2: hlo_stats converters


def _hlo_stats_converter():
    try:
        from xprof.convert import raw_to_tool_data  # noqa: PLC0415

        return raw_to_tool_data, "xprof"
    except Exception:  # noqa: BLE001 — optional dependency seam
        pass
    try:
        from tensorboard_plugin_profile.convert import (  # noqa: PLC0415
            raw_to_tool_data,
        )

        return raw_to_tool_data, "tensorboard_plugin_profile"
    except Exception:  # noqa: BLE001
        return None, None


def parse_hlo_stats_obj(obj: dict, rounds: int, backend: str = "hlo_stats",
                        stage_of: dict | None = None) -> ProfileTable:
    """Normalize an hlo_stats tool-data object (the converter output
    scripts/profile_trace.py consumed: column 2 = category, 3 = op name,
    4 = HLO text, 9 = self time us, 25 = source) into a ProfileTable."""
    rows_in = [r["c"] if isinstance(r, dict) else r for r in obj["rows"]]

    def val(r, i):
        v = r[i] if i < len(r) else None
        return v.get("v") if isinstance(v, dict) else v

    agg = {}
    for r in rows_in:
        selft = float(val(r, 9) or 0)
        name = str(val(r, 3) or "?")
        src = re.sub(r"<[^>]+>", "", str(val(r, 25) or "")).strip()
        row = agg.setdefault(
            name, [0.0, 0, str(val(r, 2) or ""), src, str(val(r, 4) or "")])
        row[0] += selft
        row[1] += 1
    rows = [
        OpRow(name=k, category=v[2],
              self_us_per_round=v[0] / max(rounds, 1),
              occurrences=v[1], source=v[3], text=v[4],
              stage=_stage(stage_of, k))
        for k, v in agg.items()
    ]
    rows.sort(key=lambda r: -r.self_us_per_round)
    return ProfileTable(
        rows=rows,
        total_us_per_round=sum(r.self_us_per_round for r in rows),
        rounds=rounds,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# capture + summarize


def summarize_logdir(logdir: str, rounds: int,
                      stage_of: dict | None = None) -> ProfileTable:
    """Summarize a captured ``jax.profiler.trace`` logdir with the first
    working backend; ``stage_of`` as in :func:`parse_xspace_bytes`."""
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError(f"no xplane.pb under {logdir}")
    conv, conv_name = _hlo_stats_converter()
    if conv is not None:
        try:
            import json

            data, _ = conv.xspace_to_tool_data(paths, "hlo_stats", {})
            obj = data if isinstance(data, dict) else json.loads(data)
            table = parse_hlo_stats_obj(obj, rounds, backend=conv_name,
                                        stage_of=stage_of)
            if table.rows:  # XLA:CPU traces convert to a table of no rows
                return table
        except Exception:  # noqa: BLE001 — converter wheels break often;
            pass           # the direct parse below reads the same trace
    blobs = [open(p, "rb").read() for p in paths]
    return parse_xspace_bytes(blobs, rounds, stage_of)


def profile_workload(
    n_peers: int,
    rounds: int = 50,
    config: str = "default",
    rounds_per_phase: int = 1,
    msg_slots: int = 64,
    heartbeat_every: int | None = None,
    unroll: int | None = None,
    logdir: str = "/tmp/pubsub_prof",
    seed: int = 0,
) -> ProfileTable:
    """Capture + summarize one profiled segment of the exact bench
    workload at an arbitrary (N, r, config) shape.

    ``rounds`` is truncated down to a whole number of phases (never to
    zero). The returned table carries the workload fingerprint so a
    recorded profile is as self-describing as a schema-v2 bench line,
    and every row the engine stage of its op in the scan's compiled
    text (``ProfileTable.by_stage``)."""
    import shutil

    import jax
    import jax.numpy as jnp

    from . import stages
    from .sweep import (
        bench_schedule,
        build_bench,
        make_bench_scan,
        workload_fingerprint,
    )

    r = max(int(rounds_per_phase), 1)
    he = heartbeat_every if heartbeat_every is not None else (r if r > 1 else 1)
    rounds = max(rounds - rounds % r, r)
    st, step, n_topics, honest = build_bench(
        n_peers, msg_slots, seed=seed, config=config, heartbeat_every=he,
        rounds_per_phase=r,
    )

    po, pt, pv = (
        jnp.asarray(a)
        for a in bench_schedule(n_peers, n_topics, honest, rounds)
    )
    scan, u = make_bench_scan(step, he, r, unroll)
    st = scan(st, po, pt, pv)  # compile + warmup
    jax.block_until_ready(st)

    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        st = scan(st, po, pt, pv)
        jax.block_until_ready(st)

    table = summarize_logdir(logdir, rounds, stages.stages_of(scan))
    table.fingerprint = workload_fingerprint(
        config, n_peers, msg_slots, he, r, seg_rounds=rounds, unroll=u)
    return table


def format_table(table: ProfileTable, top: int = 30) -> str:
    """Render the BASELINE.md-style attribution table."""
    kcat = ", ".join(
        f"{k}: {v:g}" for k, v in list(table.kernels_by_category.items())[:6]
    )
    lines = [
        f"total device self time: {table.total_us_per_round * table.rounds / 1e3:.1f} ms;"
        f" per round: {table.total_us_per_round:.0f} us"
        f"  (backend: {table.backend}, rounds: {table.rounds})",
        f"kernels/round: {table.n_kernels_per_round:.1f}  ({kcat})",
        "",
        "by category:",
    ]
    total = table.total_us_per_round or 1.0
    for k, v in sorted(table.by_category.items(), key=lambda x: -x[1]):
        lines.append(f"  {v:8.1f} us/rd {100 * v / total:5.1f}%  {k}")
    lines.append("")
    if table.by_stage:
        lines.append("by stage:")
        for k, v in sorted(table.by_stage.items(), key=lambda x: -x[1]):
            lines.append(f"  {v:8.1f} us/rd {100 * v / total:5.1f}%  {k}")
        lines.append("")
    lines.append(f"top {top} ops:")
    for r in table.top(top):
        lines.append(
            f"  {r.self_us_per_round:7.1f} us/rd {r.name:<30} "
            f"{r.stage:<13}{r.source[:80]}"
        )
        if r.text:
            lines.append(f"      {r.text[:140]}")
    return "\n".join(lines)


def main(argv=None):
    """CLI twin of the old scripts/profile_trace.py."""
    import argparse

    ap = argparse.ArgumentParser(
        description="per-op device profile of the bench workload")
    ap.add_argument("n", nargs="?", type=int, default=100_000)
    ap.add_argument("rounds", nargs="?", type=int, default=50)
    ap.add_argument("--config", default=os.environ.get("BENCH_CONFIG", "default"))
    ap.add_argument("--r", type=int,
                    default=int(os.environ.get("BENCH_PHASE_R", 1)),
                    help="rounds per phase (1 = per-round step)")
    ap.add_argument("--platform", default=os.environ.get("BENCH_PLATFORM"))
    ap.add_argument("--top", type=int, default=30)
    # honor the bench's unroll override so the captured op attribution
    # maps 1:1 onto a BENCH run measured with the same BENCH_UNROLL
    unroll_env = os.environ.get("BENCH_UNROLL")
    ap.add_argument("--unroll", type=int,
                    default=int(unroll_env) if unroll_env else None)
    args = ap.parse_args(argv)

    import jax

    from ..compile_cache import enable_persistent_cache
    from .sweep import select_platform

    stamp = select_platform(args.platform)
    prng = os.environ.get("BENCH_PRNG", "unsafe_rbg")
    if prng:
        jax.config.update("jax_default_prng_impl", prng)
    enable_persistent_cache()

    table = profile_workload(args.n, args.rounds, config=args.config,
                             rounds_per_phase=args.r, unroll=args.unroll)
    print(json.dumps({"device": stamp}))
    print(format_table(table, top=args.top))


if __name__ == "__main__":
    main()
