"""perf/ subsystem: schema-v2 artifacts, the projection engine, the
profiler's parsing layers, and the workload fingerprint.

The projection test is the load-bearing one (ISSUE round 6): the v5e-8
feasibility number that BASELINE.md rounds 3-5 computed by hand must
reproduce from code + committed artifacts, so future rounds change it by
changing inputs, not prose.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from go_libp2p_pubsub_tpu.perf import artifacts, profile, projection, sweep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# artifacts: schema v2 + legacy readers


def test_v2_readers_parse_all_committed_bench_artifacts(bench_r01_r05):
    """Every BENCH_r0*.json (the v1 driver wrappers of rounds 1-5, from
    the recorded literal; the in-tree schema-v3 scanned-window lines
    from round 15 on) must normalize through the reader — the artifact
    trajectory is the regression gate's ground truth."""
    committed = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    assert len(committed) >= 3, committed
    paths = bench_r01_r05 + committed
    recs = [artifacts.load_bench_artifact(p) for p in paths]
    for rec in recs:
        assert rec.value > 0
        # rounds 1-6 are the gossipsub headline; round 7 (round-18
        # topo-smoke) is the power-law floodsub A/B cell
        assert rec.metric.startswith(("gossipsub_v1.1_", "floodsub_"))
        assert rec.schema in (1, 2, 3)
        assert rec.config in ("default", "topo_powerlaw")
    # rounds 1-5: the 100k headline; round 6+ record their own N in the
    # fingerprint (r06 is the CPU-container scanned-window artifact)
    assert all(r.n_peers == 100_000 for r in recs[:5])
    r06_paths = [p for p, r in zip(paths, recs) if r.round_index == 6]
    if r06_paths:
        variants = artifacts.load_bench_variants(r06_paths[0])
        assert variants["parsed"].scanned is True
        assert variants["parsed"].edge_layout == "dense"  # the headline
        # the dense-vs-csr tradeoff is a committed, READABLE pair: the
        # csr cell must parse with a live value at the same shape
        csr = variants["parsed_csr"]
        assert csr.edge_layout == "csr" and csr.value > 0
        assert csr.n_peers == variants["parsed"].n_peers
        assert csr.rounds_per_phase == variants["parsed"].rounds_per_phase
    r07_paths = [p for p, r in zip(paths, recs) if r.round_index == 7]
    if r07_paths:
        variants = artifacts.load_bench_variants(r07_paths[0])
        # round 18: the headline IS the csr cell (it wins here), the
        # dense sibling stays reader-visible at the same shape
        assert variants["parsed"].edge_layout == "csr"
        assert variants["parsed"].topology_recorded
        dense = variants["parsed_dense"]
        assert dense.edge_layout == "dense" and dense.value > 0
        assert variants["parsed"].value > dense.value
    # the metric-name fallbacks recover cadence for v1 lines
    assert [r.rounds_per_phase for r in recs[:5]] == [1, 1, 1, 8, 8]
    # trajectory ordering by driver round
    assert [r.round_index for r in recs[:5]] == [1, 2, 3, 4, 5]


def test_v2_round_trip_is_lossless():
    fp = sweep.workload_fingerprint("default", 100_000, 64, 8, 8,
                                    seg_rounds=1600, unroll=16)
    rec = artifacts.BenchRecord(
        metric="gossipsub_v1.1_delivery_rounds_per_sec_n100000_phase8",
        value=1835.84, unit="delivery-rounds/s", vs_baseline=0.1836,
        schema=2, fingerprint=fp,
        extras={"heartbeats_per_sec": 229.48, "continuity_r1_ticks_per_sec": 403.89},
    )
    back = artifacts.record_from_line(json.loads(artifacts.dump_record(rec)))
    assert back == rec


def test_wrapper_with_unparsed_tail_recovers_line(tmp_path):
    """Driver wrappers whose parse failed driver-side still carry the
    line in `tail`; the reader recovers it."""
    line = {"schema": 2, "metric": "gossipsub_v1.1_heartbeat_ticks_per_sec_n100000",
            "value": 400.0, "unit": "ticks/s", "vs_baseline": 0.04}
    p = tmp_path / "BENCH_rXX.json"
    p.write_text(json.dumps({
        "n": 9, "cmd": "python bench.py", "rc": 0,
        "tail": "WARNING: something\n" + json.dumps(line) + "\n",
    }))
    rec = artifacts.load_bench_artifact(str(p))
    assert rec.value == 400.0 and rec.round_index == 9 and rec.schema == 2


def test_multichip_reader_and_bad_artifact(tmp_path):
    m = artifacts.load_multichip_artifact(os.path.join(ROOT, "MULTICHIP_r05.json"))
    assert m["ok"] is True and m["rc"] == 0
    bad = tmp_path / "x.json"
    bad.write_text(json.dumps({"foo": 1}))
    with pytest.raises(ValueError):
        artifacts.load_multichip_artifact(str(bad))


# ---------------------------------------------------------------------------
# fingerprint: the self-description must match the workload decisions


def test_fingerprint_records_elision_flags():
    # honest-net phase configs elide BOTH attribution planes
    fp = sweep.workload_fingerprint("default", 100_000, 64, 8, 8)
    assert fp["elides_invalid_message_deliveries"] is True
    assert fp["elides_mesh_message_deliveries"] is True
    assert fp["score_weights"]["invalid_message_deliveries_weight"] == 0.0
    # sybil keeps full weights — its adversary vector is what P4 catches
    fp = sweep.workload_fingerprint("sybil", 50_000, 64, 16, 16)
    assert fp["elides_invalid_message_deliveries"] is False
    assert fp["engine"]["gater"] is True
    assert fp["adversary_fraction"] == 0.2
    # elision is phase-engine-only: the r=1 continuity metric never elides
    fp = sweep.workload_fingerprint("default", 100_000, 64, 1, 1)
    assert fp["elides_invalid_message_deliveries"] is False
    assert fp["engine"]["mode"] == "per_round"


def test_fingerprint_records_engine_gating():
    # the scatter publish-allocation gate (state.py: phase + N >= 20k)
    assert sweep.workload_fingerprint("default", 100_000, 64, 8, 8)[
        "engine"]["scatter_publish_alloc"] is True
    assert sweep.workload_fingerprint("default", 12_500, 64, 16, 16)[
        "engine"]["scatter_publish_alloc"] is False
    # incremental membership planes: narrow universes, phase engine only
    assert sweep.workload_fingerprint("default", 100_000, 64, 8, 8)[
        "engine"]["incr_members"] is True
    assert sweep.workload_fingerprint("eth2", 100_000, 64, 8, 8)[
        "engine"]["incr_members"] is False
    assert sweep.workload_fingerprint("default", 100_000, 64, 1, 1)[
        "engine"]["incr_members"] is False


# ---------------------------------------------------------------------------
# projection engine


def test_projection_reproduces_round5_number(bench_r01_r05):
    """The committed round-5 projection — "~3,700-5,200 rounds/s,
    central ~4,500 ≈ 45% of the 10k north star" (BASELINE.md round-5
    addendum) — must come out of the code given the round-5 artifacts:
    BENCH_r05 (the session the 12.5k r=16 shard rate of 5,823 was
    measured in) and MULTICHIP_r05 (the collective audit whose permute
    counts the ICI term is built from)."""
    proj = projection.project_from_artifacts(
        bench_r01_r05[4],
        os.path.join(ROOT, "MULTICHIP_r05.json"),
    )
    # the file-less form regress / scan-smoke use is the same projection
    assert proj.summary() == projection.project_from_artifacts(
        None, os.path.join(ROOT, "MULTICHIP_r05.json")).summary()
    lo, central, hi = proj.rounds_per_sec
    assert 0.44 <= central / 10_000.0 <= 0.455, proj.summary()
    assert 3_600 <= lo <= 3_800, proj.summary()
    assert 5_100 <= hi <= 5_300, proj.summary()
    # the ICI band is the 0.02-0.10 ms/round the BASELINE projections used
    assert proj.ici_ms[0] == pytest.approx(0.02)
    assert proj.ici_ms[2] == pytest.approx(0.10)


def test_projection_refuses_failed_multichip(bench_r01_r05):
    """A projection built on a failed collective audit would be fiction;
    the round-1 MULTICHIP artifact (libtpu mismatch, ok=false) must be
    rejected."""
    with pytest.raises(ValueError, match="not ok"):
        projection.project_from_artifacts(
            bench_r01_r05[0],
            os.path.join(ROOT, "MULTICHIP_r01.json"),
            shard_rate=5_823.0,
        )


def test_permute_model_matches_collective_audit():
    """The ICI term's LEGACY fallback stays the 16·(r+4) formula the
    committed rounds-3..6 artifacts were projected with."""
    assert projection.permutes_per_round(8) == pytest.approx(16 * 12 / 8)  # 24
    assert projection.permutes_per_round(16) == pytest.approx(20.0)
    # at r=16 the launch-latency band gives the canonical 0.02-0.10 ms
    assert projection.ici_serialized_ms(16, 1.0) == pytest.approx(0.02)
    assert projection.ici_serialized_ms(16, 5.0) == pytest.approx(0.10)


def test_permute_model_measured_sets():
    """Round 7: a MEASURED gather-set count parameterizes the ICI term —
    the coalesced engine's r+1 sets replace the hard-coded r+4."""
    assert projection.permutes_per_round(16, 17) == pytest.approx(17.0)
    assert projection.permutes_per_round(8, 9) == pytest.approx(18.0)
    # fewer sets -> strictly cheaper ICI -> strictly higher rate
    legacy = projection.project(0.172, 16)
    coalesced = projection.project(0.172, 16, permute_sets_per_phase=17)
    assert coalesced.central > legacy.central
    assert coalesced.permute_sets_per_phase == 17
    with pytest.raises(ValueError, match="permute_sets_per_phase"):
        projection.permutes_per_round(16, 8)  # fewer sets than sub-rounds


def test_projection_uses_fingerprint_permute_sets(tmp_path, bench_r01_r05):
    """A v2 artifact carrying the measured count must project strictly
    higher than the same artifact without it (legacy fallback), with the
    dryrun gate behavior intact — and the control-set count translates
    across cadences (artifact r=8, projection r=16)."""
    import json as _json

    with open(bench_r01_r05[4]) as f:
        wrapper = _json.load(f)
    multi = os.path.join(ROOT, "MULTICHIP_r05.json")
    legacy = projection.project_from_artifacts(bench_r01_r05[4], multi)

    wrapper["parsed"]["schema"] = 2
    wrapper["parsed"]["fingerprint"] = {
        "rounds_per_phase": 8,
        "n_peers": 100_000,
        "engine": {"wire_coalesced": True},
        "permute_sets_per_phase": 9,  # the coalesced r+1 at r=8
    }
    p = tmp_path / "BENCH_r07.json"
    p.write_text(_json.dumps(wrapper))
    coalesced = projection.project_from_artifacts(str(p), multi)
    # r=8 artifact -> 1 control set -> 17 sets at the r=16 projection
    assert coalesced.permute_sets_per_phase == 17
    assert coalesced.central > legacy.central
    assert legacy.permute_sets_per_phase is None

    # reader properties
    rec = artifacts.load_bench_artifact(str(p))
    assert rec.wire_coalesced is True
    assert rec.permute_sets_per_phase == 9
    legacy_rec = artifacts.load_bench_artifact(bench_r01_r05[4])
    assert legacy_rec.wire_coalesced is None
    assert legacy_rec.permute_sets_per_phase is None

    # the dryrun gate still guards the measured-input path
    with pytest.raises(ValueError, match="not ok"):
        projection.project_from_artifacts(
            str(p), os.path.join(ROOT, "MULTICHIP_r01.json"))


def test_measured_gather_sets_coalesced_vs_legacy():
    """The fingerprint's trace-time measurement: the coalesced engine
    traces exactly r+1 halo gather sets, the legacy A/B path r+3 (wire,
    score, window; the P5 app set is weight-elided on the bench)."""
    assert sweep.measure_phase_gather_sets(
        "default", 8, wire_coalesced=True) == 9
    assert sweep.measure_phase_gather_sets(
        "default", 8, wire_coalesced=False) == 11


def test_fingerprint_records_wire_coalesced_and_permute_sets():
    fp = sweep.workload_fingerprint("default", 12_500, 64, 16, 16)
    assert fp["engine"]["wire_coalesced"] is True
    assert fp["permute_sets_per_phase"] == 17
    fp = sweep.workload_fingerprint("default", 12_500, 64, 16, 16,
                                    wire_coalesced=False)
    assert fp["engine"]["wire_coalesced"] is False
    assert fp["permute_sets_per_phase"] == 19
    # per-round cells record the engine switch but no phase permute count
    fp = sweep.workload_fingerprint("default", 100_000, 64, 1, 1)
    assert "permute_sets_per_phase" not in fp


def test_hlo_kernel_census():
    """The perf-smoke kernel gate's parser: fusion bodies and reduction
    regions don't count; bookkeeping ops don't count."""
    hlo = """\
HloModule m

%fused_computation.1 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %a = u32[8]{0} and(u32[8]{0} %p, u32[8]{0} %p)
  ROOT %b = u32[8]{0} or(u32[8]{0} %a, u32[8]{0} %a)
}

%region_0.2 (x: u32[], y: u32[]) -> u32[] {
  %x = u32[] parameter(0)
  %y = u32[] parameter(1)
  ROOT %o = u32[] or(u32[] %x, u32[] %y)
}

ENTRY %main (i: u32[8]) -> u32[8] {
  %i = u32[8]{0} parameter(0)
  %c = u32[] constant(0)
  %f = u32[8]{0} fusion(u32[8]{0} %i), kind=kLoop, calls=%fused_computation.1
  %w = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), condition=%cond, body=%body
  %r = u32[] reduce(u32[8]{0} %f, u32[] %c), dimensions={0}, to_apply=%region_0.2
  %bc = u32[8]{0} bitcast(u32[8]{0} %f)
  ROOT %cp = u32[8]{0} copy(u32[8]{0} %bc)
}
"""
    census = profile.hlo_kernel_census(hlo)
    # tuple-result kernels (while, multi-output fusions) count too
    assert census["by_op"] == {"fusion": 1, "while": 1, "reduce": 1, "copy": 1}
    assert census["total"] == 4


def test_projection_input_validation(bench_r01_r05):
    with pytest.raises(ValueError):
        projection.project(0.0, 16)
    with pytest.raises(ValueError):
        projection.permutes_per_round(0)
    # the committed shard table is r=16: a conflicting explicit cadence
    # without its own shard rate must refuse, not silently project r=16
    with pytest.raises(ValueError, match="rounds_per_phase=16"):
        projection.project_from_artifacts(
            bench_r01_r05[4],
            os.path.join(ROOT, "MULTICHIP_r05.json"),
            rounds_per_phase=8,
        )


# ---------------------------------------------------------------------------
# profiler parsing layers (pure — no trace capture)


def test_self_times_nesting():
    # a[0,100) contains b[10,30) (contains d[12,17)) and c[40,50)
    got = dict(profile._self_times(
        [(0, 100, "a"), (10, 20, "b"), (40, 10, "c"), (12, 5, "d")]))
    assert got == {"a": 70, "b": 15, "c": 10, "d": 5}


def test_parse_hlo_stats_obj():
    """The converter-backend normalizer must aggregate the hlo_stats
    column layout scripts/profile_trace.py consumed (cat=2, name=3,
    text=4, self-us=9, src=25)."""
    def row(cat, name, text, selft, src):
        r = [None] * 26
        r[2], r[3], r[4], r[9], r[25] = cat, name, text, selft, src
        return {"c": r}

    obj = {"rows": [
        row("fusion", "fusion.1", "f32[8] fusion(...)", 100.0, "a.py:1"),
        row("fusion", "fusion.1", "f32[8] fusion(...)", 50.0, "a.py:1"),
        row("copy", "copy.2", "copy(...)", 30.0, "<a href='x'>b.py:2</a>"),
    ]}
    table = profile.parse_hlo_stats_obj(obj, rounds=10)
    assert table.rows[0].name == "fusion.1"
    assert table.rows[0].self_us_per_round == pytest.approx(15.0)
    assert table.rows[0].occurrences == 2
    assert table.rows[1].source == "b.py:2"  # html stripped
    assert table.total_us_per_round == pytest.approx(18.0)
    assert table.by_category == {"fusion": 15.0, "copy": 3.0}


def test_parse_xspace_bytes_synthetic():
    """The direct-proto backend must attribute self times from a
    synthetic XSpace shaped like an XLA:CPU executor trace."""
    xplane_pb2 = profile._import_xplane_pb2()
    if xplane_pb2 is None:
        pytest.skip("no xplane proto module available")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/host:CPU")
    em_call = plane.event_metadata[1]
    em_call.id, em_call.name = 1, "call"
    em_op = plane.event_metadata[2]
    em_op.id, em_op.name = 2, "fusion.7"
    sm = plane.stat_metadata[1]
    sm.id, sm.name = 1, "hlo_op"
    line = plane.lines.add(name="tf_XLATfrtCpuClient/1")
    ev = line.events.add(metadata_id=1, offset_ps=0, duration_ps=1_000_000)
    ev.stats.add(metadata_id=1, str_value="call")
    ev2 = line.events.add(metadata_id=2, offset_ps=100, duration_ps=600_000)
    ev2.stats.add(metadata_id=1, str_value="fusion.7")
    # a python-bookkeeping line with no hlo stats must be ignored
    pl = plane.lines.add(name="python")
    pl.events.add(metadata_id=1, offset_ps=0, duration_ps=5_000_000)

    table = profile.parse_xspace_bytes([xs.SerializeToString()], rounds=2)
    got = {r.name: r for r in table.rows}
    assert set(got) == {"call", "fusion.7"}
    assert got["fusion.7"].self_us_per_round == pytest.approx(0.3)
    assert got["call"].self_us_per_round == pytest.approx(0.2)
    assert got["fusion.7"].category == "fusion"
    # the round-7 launch-count summary: 2 executed kernels over 2 rounds
    assert table.n_kernels_per_round == pytest.approx(1.0)
    assert table.kernels_by_category == {"fusion": 0.5, "call": 0.5}
    txt = profile.format_table(table)
    assert "fusion.7" in txt
    assert "kernels/round" in txt


@pytest.mark.slow
def test_profile_workload_end_to_end(tmp_path):
    """Capture + summarize a real (tiny) phase-engine segment on CPU:
    the 12.5k-shard table in docs/PERF.md is produced by this exact
    path at (12500, r=16)."""
    table = profile.profile_workload(
        256, rounds=8, config="default", rounds_per_phase=2,
        logdir=str(tmp_path / "prof"))
    assert table.rows, "no ops attributed"
    assert table.total_us_per_round > 0
    assert table.fingerprint["n_peers"] == 256
    assert table.fingerprint["rounds_per_phase"] == 2
    txt = profile.format_table(table, top=5)
    assert "by category" in txt


# ---------------------------------------------------------------------------
# sweep + regress plumbing (cheap paths only; the mini-bench itself is
# exercised by `make perf-smoke`)


def test_sweep_spec_cells():
    spec = sweep.SweepSpec(configs=("default", "eth2"), ns=(12_500, 25_000),
                           rs=(16,))
    cells = list(spec.cells())
    assert len(cells) == 4
    assert cells[0] == ("default", 12_500, 16, 16)  # he defaults to r


def test_metric_name_convention():
    assert sweep.metric_name("default", 100_000, 8) == \
        "gossipsub_v1.1_delivery_rounds_per_sec_n100000_phase8"
    assert sweep.metric_name("eth2", 12_500, 16) == \
        "gossipsub_v1.1_delivery_rounds_per_sec_n12500_eth2_phase16"
    assert sweep.metric_name("default", 100_000, 1) == \
        "gossipsub_v1.1_heartbeat_ticks_per_sec_n100000"


def test_regress_trajectory_and_projection_checks():
    from go_libp2p_pubsub_tpu.perf import regress

    assert regress.check_trajectory(ROOT) == []
    assert regress.check_projection(ROOT) == []


def test_regress_catches_corrupt_artifact(tmp_path):
    from go_libp2p_pubsub_tpu.perf import regress

    (tmp_path / "BENCH_r01.json").write_text("{not json")
    errs = regress.check_trajectory(str(tmp_path))
    assert any("BENCH_r01" in e for e in errs)


def test_adversary_and_score_weight_blocks_round_trip():
    """Round 13: the `adversary` and `score_weights` fingerprint blocks
    (ADVICE r5 item 1 for the weights) round-trip through the line
    format, and LEGACY lines read back the typed sentinels —
    ADVERSARY_OFF / SCORE_WEIGHTS_UNKNOWN, never a KeyError or a
    silently-assumed zero."""

    class _FakeAdv:
        enabled = True

        @staticmethod
        def fingerprint():
            return {"enabled": True, "n_sybils": 7,
                    "behaviors": ["drop_forward"], "onset": 3,
                    "stop": None, "promo_score": 1.0,
                    "population": "abc123"}

    fp = {
        "adversary": artifacts.adversary_fingerprint(_FakeAdv()),
        "score_weights": artifacts.score_weights_fingerprint(
            invalid_message_deliveries_weight=-1.0,
            behaviour_penalty_weight=-10.0,
        ),
    }
    rec = artifacts.BenchRecord(
        metric="attack_sybil_honest_delivery", value=1.0, unit="ratio",
        vs_baseline=0.0, schema=3, fingerprint=fp,
    )
    back = artifacts.record_from_line(json.loads(artifacts.dump_record(rec)))
    assert back.adversary_on
    assert back.adversary["n_sybils"] == 7
    assert back.adversary["behaviors"] == ["drop_forward"]
    assert back.score_weights["recorded"] is True
    assert back.score_weights["behaviour_penalty_weight"] == -10.0

    # legacy / honest lines: typed sentinels
    legacy = artifacts.record_from_line(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0})
    assert legacy.adversary == artifacts.ADVERSARY_OFF
    assert not legacy.adversary_on
    assert legacy.score_weights == artifacts.SCORE_WEIGHTS_UNKNOWN
    assert legacy.score_weights["recorded"] is False
    # the off block is explicit on new honest artifacts
    off = artifacts.adversary_fingerprint()
    assert off["enabled"] is False and off["scenario"] is None

    # every committed BENCH_r* line reads the sentinels without error
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    for p in paths:
        r = artifacts.load_bench_artifact(p)
        assert not r.adversary_on
        assert r.adversary["n_sybils"] == 0


def test_service_block_round_trips_and_legacy_sentinel():
    """Round 17: the `service` fingerprint block (the supervised
    service loop's self-description) round-trips through the line
    format, and LEGACY lines read back the typed SERVICE_OFF sentinel
    — never a KeyError or a silently-assumed bare run."""
    fp = {
        "service": artifacts.service_fingerprint(
            segment_rounds=8, keep_last=3, keep_every=4,
            probes=("finite-state", "events-monotone"),
            recoveries=2, segments=40, resumes=1),
    }
    rec = artifacts.BenchRecord(
        metric="service_loop_rounds_per_sec", value=32.0, unit="rounds/s",
        vs_baseline=0.0, schema=3, fingerprint=fp,
    )
    back = artifacts.record_from_line(json.loads(artifacts.dump_record(rec)))
    assert back.service_on
    assert back.service["segment_rounds"] == 8
    assert back.service["retention"] == {"keep_last": 3, "keep_every": 4}
    assert back.service["probes"] == ["finite-state", "events-monotone"]
    assert back.service["recoveries"] == 2 and back.service["resumes"] == 1

    legacy = artifacts.record_from_line(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0})
    assert legacy.service == artifacts.SERVICE_OFF
    assert not legacy.service_on

    # every committed BENCH_r* line reads the sentinel without error
    for p in sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json"))):
        r = artifacts.load_bench_artifact(p)
        assert r.service["enabled"] is False


def test_topology_block_round_trips_and_legacy_sentinel():
    """Round 18: the `topology` fingerprint block (which generated
    graph a cell ran on) round-trips through the line format; LEGACY
    lines read back the typed TOPOLOGY_BANDED sentinel (the banded
    bench ring, recorded: false) — never a KeyError."""
    fp = {
        "topology": artifacts.topology_fingerprint(
            generator="powerlaw", family="power-law",
            params={"exponent": 2.2, "d_min": 2, "max_degree": 64},
            n_edges=10186, mean_degree=4.97, max_degree=61,
            density=0.078, seed=0,
            link_classes={"local": 100, "regional": 40, "global": 10},
            workload_pattern="attestation_storm"),
    }
    rec = artifacts.BenchRecord(
        metric="powerlaw_rounds_per_sec", value=117.0,
        unit="delivery-rounds/s", vs_baseline=0.0117, schema=3,
        fingerprint=fp,
    )
    back = artifacts.record_from_line(json.loads(artifacts.dump_record(rec)))
    assert back.topology_recorded
    assert back.topology["generator"] == "powerlaw"
    assert back.topology["n_edges"] == 10186
    assert back.topology["density"] == pytest.approx(0.078)
    assert back.topology["workload_pattern"] == "attestation_storm"
    assert back.topology["link_classes"]["regional"] == 40

    legacy = artifacts.record_from_line(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0})
    assert legacy.topology == artifacts.TOPOLOGY_BANDED
    assert not legacy.topology_recorded
    assert legacy.topology["generator"] == "ring_lattice"

    # the committed BENCH_r07 pair carries the block; every earlier
    # committed line reads the sentinel without error
    variants = artifacts.load_bench_variants(
        os.path.join(ROOT, "BENCH_r07.json"))
    assert variants["parsed"].topology_recorded
    assert variants["parsed"].edge_layout == "csr"
    assert variants["parsed_dense"].topology == variants["parsed"].topology
    for p in sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json"))):
        r = artifacts.load_bench_artifact(p)
        assert isinstance(r.topology["generator"], str)


def test_service_report_fingerprint_matches_block(tmp_path):
    """ServiceReport.fingerprint() emits exactly the artifacts block
    shape (the execution/params-block pattern), and tracestat's
    artifact reader surfaces it."""
    import sys

    from go_libp2p_pubsub_tpu.oracle import probes as _probes
    from go_libp2p_pubsub_tpu.serve import RetentionPolicy
    from go_libp2p_pubsub_tpu.serve.supervisor import ServiceReport

    rep = ServiceReport(
        states=None, n_dispatches=16, rounds=16, segments=4,
        segment_rounds=4, seconds=1.0, recoveries=1, retries=2,
        degradations=[], resumed_from=8, window_compiles={"L4": 1},
        checkpoints=[], heartbeat_path="", invariant_checks=4,
        probes=_probes.HealthConfig().names,
        retention=RetentionPolicy(keep_last=2, keep_every=3), bundles=[])
    block = rep.fingerprint()
    assert block["enabled"] and block["segment_rounds"] == 4
    assert block["retention"] == {"keep_last": 2, "keep_every": 3}
    assert block["resumes"] == 1

    rec = artifacts.BenchRecord(
        metric="m", value=1.0, unit="x", vs_baseline=0.0, schema=3,
        fingerprint={"service": block})
    art = tmp_path / "svc.json"
    art.write_text(artifacts.dump_record(rec) + "\n")
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        from tracestat import artifact_service

        got = artifact_service(str(art))
    finally:
        sys.path.pop(0)
    assert got == block


def test_service_off_sentinel_is_mutation_safe():
    """Review regression: SERVICE_OFF is the only sentinel with nested
    containers — a caller mutating a legacy record's service block must
    not corrupt the module default for later reads."""
    legacy = artifacts.record_from_line(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0})
    sv = legacy.service
    sv["retention"]["keep_last"] = 99
    sv["probes"].append("bogus")
    fresh = artifacts.record_from_line(
        {"metric": "m2", "value": 1.0, "unit": "x",
         "vs_baseline": 0.0}).service
    assert fresh["retention"] == {"keep_last": 0, "keep_every": 0}
    assert fresh["probes"] == []


def test_router_block_round_trips_and_legacy_sentinel(tmp_path):
    """Round 24: the `router` fingerprint block (which protocol
    generation cut the number — v1.1 | v1.2-IDONTWANT — plus the choke
    decision rule and latency ring depth) round-trips through the line
    format; LEGACY lines read back the typed ROUTER_V11 sentinel (plain
    v1.1 semantics — literally what every pre-round-24 build ran), and
    tracestat's artifact reader surfaces the block."""
    import sys

    from go_libp2p_pubsub_tpu.routers import RouterConfig

    rc = RouterConfig(idontwant=True, choke=True, latency_rounds=7,
                      choke_threshold=0.35, unchoke_threshold=0.1)
    block = artifacts.router_fingerprint(rc)
    assert block["enabled"] and block["protocol"] == "v1.2"
    assert block["idontwant"] and block["choke"]
    assert block["latency_rounds"] == 7
    assert block["choke_threshold"] == pytest.approx(0.35)
    assert block["choke_max_per_hb"] == 1

    rec = artifacts.BenchRecord(
        metric="choke_dup_ratio", value=0.2, unit="dup/delivery",
        vs_baseline=0.0, schema=3, fingerprint={"router": block})
    back = artifacts.record_from_line(json.loads(artifacts.dump_record(rec)))
    assert back.router_on
    assert back.router == block

    # router=None IS the explicit v1.1 block (what the sweep emits for
    # every bench cell), and a latency-only build stays protocol v1.1
    # with its choke knobs typed-None, not garbage defaults
    assert artifacts.router_fingerprint(None) == artifacts.ROUTER_V11
    lat = artifacts.router_fingerprint(RouterConfig(latency_rounds=3))
    assert lat["enabled"] and lat["protocol"] == "v1.1"
    assert lat["latency_rounds"] == 3 and lat["choke_ema_alpha"] is None
    fp = sweep.workload_fingerprint("default", 100_000, 64, 8, 8)
    assert fp["router"] == artifacts.ROUTER_V11

    legacy = artifacts.record_from_line(
        {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0})
    assert legacy.router == artifacts.ROUTER_V11
    assert not legacy.router_on
    assert legacy.router["protocol"] == "v1.1"

    # tracestat surfaces the block; every committed BENCH_r* line reads
    # the sentinel without error
    art = tmp_path / "router.json"
    art.write_text(artifacts.dump_record(rec) + "\n")
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        from tracestat import artifact_router

        got = artifact_router(str(art))
    finally:
        sys.path.pop(0)
    assert got == block
    for p in sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json"))):
        r = artifacts.load_bench_artifact(p)
        assert not r.router_on and r.router["protocol"] == "v1.1"
