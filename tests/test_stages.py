"""The engine's stages (perf/stages.py): the scope names, the way from a
compiled window to a stage map, the registry of traced windows, and the
persistent-cache trap the versioned window name exists for."""

import contextlib
import dataclasses
import gc
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import driver, graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.parallel import make_mesh, shard_state
from go_libp2p_pubsub_tpu.perf import profile, stages
from go_libp2p_pubsub_tpu.state import Net

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "go_libp2p_pubsub_tpu")
N, R, M, P = 64, 4, 64, 4


@pytest.mark.parametrize("op_name,stage", [
    ("jit(gs_window_v1)/while/body/closed_call/gs.data_round/"
     "gs.edge_gather/jit(_take)/and", "edge_gather"),
    ("jit(gs_window_v1)/while/body/closed_call/gs.phase_tail/gs.heartbeat/"
     "gs.score/mul", "score"),
    ("jit(gs_window_v1)/while/body/closed_call/gs.control_head/add",
     "control_head"),
    ("jit(gs_window_v1)/vmap(gs.data_round)/vmap(gs.deliver)/or", "deliver"),
    ("jit(gs_window_v1)/while/body/closed_call/jit(step)", "unscoped"),
    ("jit(gs_window_v1)/while", "unscoped"),
    # the window's own name is no scope, nor is a name STAGES lacks
    ("jit(gs_window_v1)/gs_window/add", "unscoped"),
    ("jit(f)/gs.pub_plan/gs.not_a_stage/add", "pub_plan"),
    ("", "unscoped"),
])
def test_stage_of_takes_the_innermost_scope(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_scope_refuses_a_name_outside_stages():
    assert stages.window_name() == f"gs_window_v{stages.VERSION}"
    with pytest.raises(ValueError, match="no stage"):
        stages.scope("gather")


#: shaped like XLA's compiled text: a fusion with metadata of its own and a
#: body whose parameters have none, a ``while`` with body and condition, a
#: called computation, ``ROOT`` lines, tuple types, names with and without
#: the ``%`` sigil
HLO = '''HloModule jit_gs_window_v1, is_scheduled=true, entry_computation_layout={(u32[8]{0})->u32[8]{0}}

%fused_computation.1 (param_0.3: u32[8]) -> u32[8] {
  %param_0.3 = u32[8]{0} parameter(0)
  ROOT %and.7 = u32[8]{0} and(%param_0.3, %param_0.3), metadata={op_name="jit(gs_window_v1)/while/body/closed_call/gs.data_round/gs.edge_gather/jit(_take)/and" source_file="state.py" source_line=98}
}

%closed_call.5 (arg.1: u32[8]) -> u32[8] {
  %arg.1 = u32[8]{0} parameter(0)
  %and_bitcast_fusion = u32[8]{0} fusion(%arg.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(gs_window_v1)/while/body/closed_call/gs.data_round/gs.edge_gather/jit(_take)/and"}
  ROOT %add.9 = u32[8]{0} add(%and_bitcast_fusion, %arg.1), metadata={op_name="jit(gs_window_v1)/while/body/closed_call/gs.phase_tail/add"}
}

%body.2 (p: (s32[], u32[8])) -> (s32[], u32[8]) {
  %p = (s32[], u32[8]{0}) parameter(0)
  %get-tuple-element.4 = u32[8]{0} get-tuple-element(%p), index=1
  %copy.12 = u32[8]{0} copy(%get-tuple-element.4)
  %call.3 = u32[8]{0} call(%copy.12), to_apply=%closed_call.5, metadata={op_name="jit(gs_window_v1)/while/body/closed_call"}
  concatenate.2161 = u32[16]{0} concatenate(%call.3, %call.3), dimensions={0}, metadata={op_name="jit(gs_window_v1)/while/body/closed_call/gs.data_round/gs.deliver/concatenate"}
  ROOT %tuple.6 = (s32[], u32[8]{0}) tuple(%get-tuple-element.4, %call.3)
}

%cond.3 (p.1: (s32[], u32[8])) -> pred[] {
  %p.1 = (s32[], u32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%p.1, %p.1), direction=LT, metadata={op_name="jit(gs_window_v1)/while/cond/lt"}
}

ENTRY %main.10 (st: u32[8]) -> u32[8] {
  %st = u32[8]{0} parameter(0), metadata={op_name="st"}
  %while.8 = (s32[], u32[8]{0}) while(%st), condition=%cond.3, body=%body.2, metadata={op_name="jit(gs_window_v1)/while"}
  ROOT %get-tuple-element.11 = u32[8]{0} get-tuple-element(%while.8), index=1, metadata={op_name="jit(gs_window_v1)/while"}
}
'''


def test_instruction_stages_on_a_recorded_text():
    got = stages.instruction_stages(HLO)
    assert got == {
        "param_0.3": "unscoped", "and.7": "edge_gather",
        "arg.1": "unscoped", "and_bitcast_fusion": "edge_gather",
        "add.9": "phase_tail",
        "p": "unscoped", "get-tuple-element.4": "unscoped",
        "copy.12": "unscoped", "call.3": "unscoped",
        "concatenate.2161": "deliver", "tuple.6": "unscoped",
        "p.1": "unscoped", "lt.1": "unscoped",
        "st": "unscoped", "while.8": "unscoped",
        "get-tuple-element.11": "unscoped",
    }


def _toy(scored: bool, n_topics: int):
    """A phase step and a fresh state at toy size, built as the benchmark
    builds its cells (tracer-detached, no fanout slots)."""
    topo = graph.random_connect(N, 6, seed=1)
    net = Net.build(topo, graph.subscribe_all(N, n_topics))
    sp = None
    if scored:
        tp = TopicScoreParams(mesh_message_deliveries_weight=0.0,
                              mesh_failure_penalty_weight=0.0,
                              invalid_message_deliveries_weight=0.0)
        sp = PeerScoreParams(topics={t: tp for t in range(n_topics)},
                             skip_app_specific=True)
    cfg = GossipSubConfig.build(
        dataclasses.replace(GossipSubParams(), flood_publish=False),
        PeerScoreThresholds(), score_enabled=scored, heartbeat_every=R)
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)
    step = make_gossipsub_phase_step(cfg, net, R, score_params=sp)

    def fresh():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=3)

    return step, fresh


def _schedule(rounds: int, n_topics: int):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, N, (rounds, P)).astype(np.int32)),
            jnp.asarray(rng.integers(0, n_topics, (rounds, P)).astype(np.int32)),
            jnp.ones((rounds, P), bool))


def _scan(step):
    return driver.make_scan(step, heartbeat_every=R, rounds_per_phase=R,
                            static_heartbeat=True)


def _entries_of(jitted):
    return [w for w in stages.traced_windows() if w.jitted is jitted]


def _kernel_stages(hlo_text: str) -> list:
    """The stage of every instruction that launches: those of the entry,
    ``while`` bodies and called computations, fusion bodies and reducer
    regions left out (``perf.profile.hlo_kernel_census``'s cut)."""
    out = []
    for comp in re.split(r"\n(?=%|ENTRY)", hlo_text):
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", comp)
        if (name is None or "fused_computation" in name.group(1)
                or name.group(1).startswith("region")):
            continue
        for line in comp.splitlines()[1:]:
            op = re.search(r"= (?:\([^)]*\)|\S+?) ([\w\-]+)\(", line)
            if op and op.group(1) not in profile._NON_KERNEL_OPS:
                out.extend(stages.instruction_stages(line).values())
    return out


#: XLA:CPU's own instructions in the toy window (the scan's carry copies,
#: the expanded ``rng-bit-generator``, the ``while``) are 17-18 % of those
#: that launch; a scope lost from a stage shows as a stage without
#: instructions first, and as a share over this second
UNSCOPED_SHARE_MAX = 0.30


@pytest.mark.parametrize("scored,n_topics", [(True, 1), (False, 8)],
                         ids=["scored-t1", "unscored-t8"])
def test_every_stage_owns_instructions_of_a_toy_window(scored, n_topics):
    step, fresh = _toy(scored, n_topics)
    scan = _scan(step)
    text = scan.lower(fresh(), *_schedule(2 * R, n_topics)).compile().as_text()
    assert text.startswith(f"HloModule jit_{stages.window_name()}")
    owned = set(stages.instruction_stages(text).values())
    # with scoring off nothing of score/engine.py is traced
    want = set(stages.STAGES) - (set() if scored else {"score"})
    assert owned == want | {stages.UNSCOPED}
    launched = _kernel_stages(text)
    assert len(launched) > 300
    share = launched.count(stages.UNSCOPED) / len(launched)
    assert share < UNSCOPED_SHARE_MAX, share


def test_a_window_is_noted_once_and_its_map_costs_no_dispatch_cache_entry():
    step, fresh = _toy(True, 1)
    scan = _scan(step)
    xs = _schedule(2 * R, 1)
    assert _entries_of(scan) == []              # made, not yet traced
    st = scan(fresh(), *xs)
    (entry,) = _entries_of(scan)
    assert entry.module_name == "jit_gs_window_v1" and not entry.sharded
    for _ in range(10):
        st = scan(st, *xs)
    jax.block_until_ready(st)
    assert _entries_of(scan) == [entry]
    size = scan._cache_size()
    assert size == 1
    stage_of = entry.stages()
    assert entry.stages() is stage_of           # memoised
    assert set(stage_of.values()) == set(stages.STAGES) | {stages.UNSCOPED}
    # the lowering traced the body again: still one entry, and the
    # dispatch cache is as it was
    assert _entries_of(scan) == [entry] and scan._cache_size() == size
    # the step inside is no window of its own, nor is make_scan's inner body
    assert all(w.module_name == "jit_gs_window_v1"
               for w in stages.traced_windows())
    # another shape is another program of the same window
    scan(st, *_schedule(4 * R, 1))
    assert len(_entries_of(scan)) == 2


def test_the_registry_outlives_the_loop_and_keeps_the_newest_few():
    """The trace is read after the loop that made the window returned, so
    the registry holds the window; it holds ``KEPT_WINDOWS``, no more."""
    def step(st, x):
        with stages.scope("deliver"):
            return st * x

    def loop(width):
        win = driver.make_window(step, donate=False)
        win(jnp.ones((width,)), (jnp.ones((2, width)),))
        return id(win)

    ids = [loop(w) for w in range(1, stages.KEPT_WINDOWS + 4)]
    gc.collect()
    kept = stages.traced_windows()
    assert [id(w.jitted) for w in kept] == ids[-stages.KEPT_WINDOWS:]
    assert "deliver" in kept[-1].stages().values()


def test_make_window_is_noted_with_its_keywords():
    def step(st, x):
        with stages.scope("data_round"):
            return st + x

    win = driver.make_window(step, observe=lambda s: s.sum(), donate=False)
    _, ys = win(jnp.zeros((4,)), (jnp.ones((3, 4)),), due=None, consts=())
    assert ys["obs"].shape == (3,)
    (entry,) = _entries_of(win)
    assert "data_round" in entry.stages().values()
    with jax.disable_jit():
        win(jnp.zeros((4,)), (jnp.ones((5, 4)),))
    assert _entries_of(win) == [entry]          # nothing traced, nothing noted


def test_a_window_over_two_devices_is_marked_and_yields_no_map():
    step, fresh = _toy(True, 1)
    scan = _scan(step)
    st = shard_state(fresh(), make_mesh(devices=jax.devices()[:2]), N)
    jax.block_until_ready(scan(st, *_schedule(2 * R, 1)))
    (entry,) = _entries_of(scan)
    assert entry.sharded and entry.stages() is None


@pytest.fixture
def fresh_cache_dir(tmp_path):
    """A persistent compilation cache of this test's own that keeps every
    program, however fast it compiled."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0.0, 0, True)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_an_executable_compiled_before_the_scopes_is_never_loaded(
        fresh_cache_dir, monkeypatch):
    """JAX's persistent cache key strips debug info, and a named scope is
    debug info: the same program under the same module name is a HIT, and
    the text that comes back is the old one, without a scope. The window's
    versioned name keeps it out."""
    def toy_step(scoped: bool):
        def step(st, x):
            for i, s in enumerate(stages.STAGES):
                with stages.scope(s) if scoped else contextlib.nullcontext():
                    st = jnp.sin(st * (i + 2)) + x
            return st
        return step

    args = (jnp.arange(8.0), (jnp.ones((4, 8)),))
    entries = lambda: sorted(os.listdir(fresh_cache_dir))

    # the parent's program: no scopes, window body named as before this PR
    monkeypatch.setattr(stages, "window_name", lambda: "run")
    before = driver.make_window(toy_step(False), donate=False)
    assert "gs." not in before.lower(*args).compile().as_text()
    held = entries()
    assert held
    # the trap: scoped, same name -> a hit, nothing written, no scope
    trapped = driver.make_window(toy_step(True), donate=False)
    trapped(*args)
    (entry,) = _entries_of(trapped)
    if entries() != held:
        pytest.skip("this JAX keys the persistent cache on debug info")
    assert set(entry.stages().values()) == {stages.UNSCOPED}

    # the cure: the window named from the scopes' version
    monkeypatch.undo()
    scoped = driver.make_window(toy_step(True), donate=False)
    scoped(*args)
    (entry,) = _entries_of(scoped)
    assert set(stages.STAGES) <= set(entry.stages().values())
    assert len(entries()) > len(held)
    # and loaded back from the cache, the text still holds the scopes
    again = driver.make_window(toy_step(True), donate=False)
    written = entries()
    assert set(stages.STAGES) <= set(stages.instruction_stages(
        again.lower(*args).compile().as_text()).values())
    assert entries() == written


def test_no_module_but_stages_spells_a_scope_name():
    """Every scope of the package is ``stages.scope(<one of STAGES>)``."""
    literal = re.compile(r"named_scope\(|TraceAnnotation\(|[\"']gs\.")
    used = set()
    for root, _, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            if path != os.path.join(PKG, "perf", "stages.py"):
                assert not literal.search(src), path
            used.update(re.findall(r"stages?\.scope\(\"(\w+)\"\)", src))
            used.update(re.findall(r"\bstage\(\"(\w+)\"\)", src))
    assert used == set(stages.STAGES)


def test_by_stage_sums_to_the_table_total():
    """``ProfileTable.by_stage`` on the synthetic XSpace of
    tests/test_perf.py: every op's self time lands in one stage."""
    xplane_pb2 = profile._import_xplane_pb2()
    if xplane_pb2 is None:
        pytest.skip("no xplane proto module available")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name="/host:CPU")
    sm = plane.stat_metadata[1]
    sm.id, sm.name = 1, "hlo_op"
    line = plane.lines.add(name="tf_XLATfrtCpuClient/1")
    for i, (name, start, dur) in enumerate(
            [("call", 0, 1_000_000), ("fusion.7", 100, 600_000),
             ("copy.3", 700_000, 100_000)], start=1):
        em = plane.event_metadata[i]
        em.id, em.name = i, name
        ev = line.events.add(metadata_id=i, offset_ps=start, duration_ps=dur)
        ev.stats.add(metadata_id=1, str_value=name)
    stage_of = {"call": "unscoped", "fusion.7": "edge_gather"}
    table = profile.parse_xspace_bytes([xs.SerializeToString()], rounds=2,
                                       stage_of=stage_of)
    assert {r.name: r.stage for r in table.rows} == {
        "call": "unscoped", "fusion.7": "edge_gather", "copy.3": "unscoped"}
    assert table.by_stage == pytest.approx(
        {"edge_gather": 0.3, "unscoped": 0.2})
    assert sum(table.by_stage.values()) == pytest.approx(
        table.total_us_per_round)
    txt = profile.format_table(table)
    assert txt.index("by stage:") < txt.index("top 30 ops:")
    assert "edge_gather" in txt
    # without a map the table has no stage cut, and says so by leaving it out
    bare = profile.parse_xspace_bytes([xs.SerializeToString()], rounds=2)
    assert bare.by_stage == {} and "by stage:" not in profile.format_table(bare)
