"""The served loop's host spans (serve/supervisor.py through
perf/spans.py): a committed segment's report row carries its host
milliseconds by span, on the span's own clock."""

import json

import pytest

from go_libp2p_pubsub_tpu import ensemble
from go_libp2p_pubsub_tpu.perf import spans, stages
from go_libp2p_pubsub_tpu.serve import ServiceConfig, Supervisor
from go_libp2p_pubsub_tpu.serve._child import build_cell
from go_libp2p_pubsub_tpu.serve.supervisor import ROW_SPANS

N, ROUNDS, SEG, SEED, LOSS = 48, 16, 4, 7, 0.1
INSIDE = ("stack_args", "dispatch", "probe_readback", "ev_drain",
          "checkpoint_save")


@pytest.fixture(scope="module")
def cell():
    return build_cell(N, ROUNDS, SEED, LOSS)


@pytest.mark.parametrize("drain", [False, True], ids=["plain", "ev-drain"])
def test_a_row_holds_its_segment_s_host_ms(cell, tmp_path, drain):
    step, make_args, template_fn, _net, _cfg = cell
    spans.clear()
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     ServiceConfig(n_dispatches=ROUNDS, segment_len=SEG,
                                   report_name="service",
                                   drain_event_counters=drain))
    rep = sup.run(fresh=True)
    rows = [json.loads(x) for x in open(tmp_path / "service.jsonl")]
    assert len(rows) == rep.segments == ROUNDS // SEG
    got = spans.recorded()
    segments = [s for s in got if s.name == "serve.segment"]
    assert [s.attrs["segment"] for s in segments] == list(range(len(rows)))
    assert len(ROW_SPANS) == 7
    for row, seg in zip(rows, segments):
        host = row["host_ms"]
        assert tuple(host) == ROW_SPANS
        assert all(v >= 0.0 for v in host.values())
        # `seconds` is the span's duration, as the row rounds it
        took = (seg.end_ns - seg.start_ns) * 1e-9
        assert row["seconds"] == round(took, 4)
        # what lies inside the segment's span is its children's time
        kids = [s for s in got if s.parent == seg.id]
        assert {s.name[len("serve."):] for s in kids} <= set(INSIDE)
        inside = sum(host[k] for k in INSIDE)
        if row["segment"] > 0:        # the first one stacked before its clock
            assert inside == pytest.approx(
                sum(s.end_ns - s.start_ns for s in kids) * 1e-6, abs=0.01)
        assert sum(s.end_ns - s.start_ns for s in kids) * 1e-9 <= took
        assert host["dispatch"] > 0 and host["probe_readback"] > 0
        assert host["checkpoint_save"] > 0 and host["heartbeat_write"] > 0
        assert (host["ev_drain"] > 0) == drain
    # a row cannot time its own write: it carries the one before it
    assert rows[0]["host_ms"]["report_row"] == 0.0
    assert all(r["host_ms"]["report_row"] > 0 for r in rows[1:])
    # the last segment stacks nothing ahead; the first one stacked twice
    assert rows[-1]["host_ms"]["stack_args"] == 0.0
    assert rows[0]["host_ms"]["stack_args"] > 0
    names = {s.name for s in got}
    assert {"serve." + k for k in ROW_SPANS} - names == (
        set() if drain else {"serve.ev_drain"})
    assert "serve.restore" not in names        # a fresh run restores nothing
    assert rep.seconds >= sum(r["seconds"] for r in rows) - 1e-3

    # a resumed run restores under its span, and runs nothing again
    spans.clear()
    again = Supervisor(step, make_args, template_fn, str(tmp_path),
                       ServiceConfig(n_dispatches=ROUNDS, segment_len=SEG,
                                     report_name="service",
                                     drain_event_counters=drain)).run()
    assert again.resumed_from == ROUNDS and again.segments == 0
    assert [s.name for s in spans.recorded() if s.name.startswith("serve.")
            ] == ["serve.restore", "serve.heartbeat_write",
                  "serve.heartbeat_write"]


def test_the_ensemble_window_s_seconds_are_its_span_s(cell):
    step, make_args, template_fn, _net, _cfg = cell
    spans.clear()
    run = ensemble.WindowRunner(step, ROUNDS, segment_len=SEG).run(
        template_fn(), make_args)
    (ran,) = [s for s in spans.recorded() if s.name == "ensemble.run"]
    assert run.seconds == (ran.end_ns - ran.start_ns) * 1e-9 > 0
    assert run.dispatches == ROUNDS // SEG
    # the window is compiled inside it, once
    (ours,) = [s for s in spans.recorded() if s.name == "compile.backend"
               and stages.window_name() in s.attrs["fun_name"]]
    assert ours.parent == ran.id
