"""The program's host-span recorder (perf/spans.py): what a span records,
what it turns of ``jax.monitoring`` into spans and counters, where the
set-up's spans sit in the program, and that it is the one recorder."""

import dataclasses
import functools
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import compile_cache, driver, graph
from go_libp2p_pubsub_tpu.config import GossipSubParams, PeerScoreThresholds
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.perf import spans, stages
from go_libp2p_pubsub_tpu.state import Net

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "go_libp2p_pubsub_tpu")
N, R, M, P = 256, 4, 64, 4
COMPILE = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture
def recorder():
    spans.clear()
    yield spans
    spans.clear()


def _named(recorded, name):
    return [s for s in recorded if s.name == name]


def test_nesting_gives_parent_and_ids_are_unique(recorder):
    with spans.span("serve.segment", segment=7) as outer:
        with spans.span("serve.dispatch") as first:
            pass
        with spans.span("serve.stack_args") as second:
            with spans.span("serve.probe_readback") as inner:
                pass
    got = {s.name: s for s in spans.recorded()}
    assert [s.name for s in spans.recorded()] == [
        "serve.dispatch", "serve.probe_readback", "serve.stack_args",
        "serve.segment"]                       # in the order they ended
    assert got["serve.segment"].parent is None
    assert got["serve.dispatch"].parent == outer.id
    assert got["serve.stack_args"].parent == outer.id
    assert got["serve.probe_readback"].parent == second.id
    assert got["serve.segment"].attrs == {"segment": 7}
    ids = [outer.id, first.id, second.id, inner.id]
    assert len(set(ids)) == 4 and ids == sorted(ids)
    for s in spans.recorded():
        assert isinstance(s, tuple) and len(s) == 6
        assert s.start_ns <= s.end_ns
    o, i = got["serve.segment"], got["serve.probe_readback"]
    assert o.start_ns <= i.start_ns and i.end_ns <= o.end_ns
    assert outer.seconds == (o.end_ns - o.start_ns) * 1e-9
    # nothing is open once the outermost has ended
    with spans.span("serve.restore"):
        pass
    assert spans.recorded()[-1].parent is None


def test_a_span_that_raises_is_recorded_and_closed(recorder):
    with pytest.raises(KeyError):
        with spans.span("serve.segment"):
            with spans.span("serve.dispatch"):
                raise KeyError("boom")
    assert [s.name for s in spans.recorded()] == ["serve.dispatch",
                                                  "serve.segment"]
    with spans.span("serve.restore"):
        pass
    assert spans.recorded()[-1].parent is None


def test_a_decorated_function_records_a_span_per_call(recorder):
    @spans.span("ensemble.run", who="test")
    def work(x):
        """doc"""
        return x + 1

    assert work.__name__ == "work" and work.__doc__ == "doc"
    assert [work(1), work(2)] == [2, 3]
    a, b = spans.recorded()
    assert a.name == b.name == "ensemble.run" and a.id != b.id
    assert a.end_ns <= b.start_ns and a.attrs == {"who": "test"}


@pytest.mark.parametrize("name", ["setup", "setup.net_build.", "gs.host.x",
                                  "compile", "", "serve.segment "])
def test_an_unknown_name_raises(name):
    with pytest.raises(ValueError, match="no span"):
        spans.span(name)


@pytest.mark.parametrize("name", spans.SPANS)
def test_every_name_is_a_dotted_slug_and_opens(name, recorder):
    assert re.fullmatch(r"[a-z_]+(\.[a-z_]+)+", name)
    with spans.span(name):
        pass
    (s,) = spans.recorded()
    assert s.name == name and s.parent is None and s.attrs == {}


def test_the_listeners_record_only_names_of_spans():
    made = list(spans._JAX.values())
    assert set(made) <= set(spans.SPANS) and len(set(made)) == len(made)
    assert set(spans._COUNTED.values()) <= set(made)
    assert set(spans._COUNTED) == set(spans.counts())
    assert len(set(spans.SPANS)) == len(spans.SPANS)


def test_the_ring_is_bounded_and_clear_empties_it(recorder):
    for i in range(spans.KEPT_SPANS + 50):
        with spans.span("serve.dispatch", i=i):
            pass
    kept = spans.recorded()
    assert len(kept) == spans.KEPT_SPANS == 4096
    assert [s.attrs["i"] for s in kept[:2]] == [50, 51]     # the newest
    assert kept[-1].attrs["i"] == spans.KEPT_SPANS + 49
    spans.clear()
    assert spans.recorded() == []
    assert spans.counts() == {"programs_compiled": 0, "cache_hits": 0,
                              "cache_misses": 0}


def test_watch_compiles_registers_once():
    from jax._src import monitoring

    spans.watch_compiles()
    compile_cache.enable_persistent_cache()
    with spans.span("serve.restore"):
        pass
    spans.watch_compiles()
    assert monitoring.get_event_duration_listeners().count(
        spans._on_duration) == 1
    assert monitoring.get_event_listeners().count(spans._on_event) == 1


def test_a_fresh_jit_is_three_spans_under_the_open_one(recorder):
    def spans_probe_fn(x):
        # inner jits (``jnp``'s own) are traced inside: only the outermost
        # trace is kept
        return jnp.where(x > 0, x, -x) + jnp.sum(x)

    x = jnp.arange(8.0)
    spans.clear()                           # ``arange`` compiled a program
    fn = jax.jit(spans_probe_fn)
    with spans.span("setup.step_build") as outer:
        fn(x)
    got = spans.recorded()
    mine = [s for s in got if "spans_probe_fn" in s.attrs.get("fun_name", "")]
    assert [s.name for s in mine] == list(COMPILE)
    assert all(s.parent == outer.id for s in mine)
    assert not [s for s in got
                if s.name == "compile.trace" and s not in mine]
    # they lie inside the span and in their order, on the span's clock
    o = _named(got, "setup.step_build")[0]
    assert o.start_ns <= mine[0].start_ns and mine[-1].end_ns <= o.end_ns
    assert mine[0].end_ns <= mine[1].end_ns <= mine[2].start_ns + 1_000_000
    assert spans.counts()["programs_compiled"] == 1

    # a cached call emits nothing
    y = x + 1.0
    before = len(spans.recorded())
    with spans.span("setup.step_build"):
        fn(x)
        fn(y)
    assert [s.name for s in spans.recorded()[before:]] == ["setup.step_build"]


def test_a_cold_compile_is_a_miss_and_the_next_a_hit(recorder, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    held = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def spans_cache_fn(x):
            return x * 3.0 + 1.0

        x = jnp.arange(16.0)
        spans.clear()
        with spans.span("setup.state_init") as outer:
            jax.jit(spans_cache_fn)(x)
        assert spans.counts() == {"programs_compiled": 1, "cache_hits": 0,
                                  "cache_misses": 1}
        (miss,) = _named(spans.recorded(), "compile.cache_miss")
        assert miss.parent == outer.id and miss.start_ns == miss.end_ns
        (backend,) = _named(spans.recorded(), "compile.backend")
        assert miss.end_ns <= backend.end_ns          # inside its compile
        assert os.listdir(tmp_path)

        jax.clear_caches()
        spans.clear()
        with spans.span("setup.state_init") as outer:
            jax.jit(spans_cache_fn)(x)
        assert spans.counts() == {"programs_compiled": 1, "cache_hits": 1,
                                  "cache_misses": 0}
        names = [s.name for s in spans.recorded()]
        for name in ("compile.cache_hit", "compile.backend"):
            assert names.count(name) == 1, name
        assert "compile.cache_miss" not in names
        (hit,) = _named(spans.recorded(), "compile.cache_hit")
        assert hit.parent == outer.id and hit.start_ns == hit.end_ns
    finally:
        for k, v in held.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _toy():
    topo = graph.random_connect(N, 6, seed=1)
    net = Net.build(topo, graph.subscribe_all(N, 1))
    cfg = GossipSubConfig.build(
        dataclasses.replace(GossipSubParams(), flood_publish=False),
        PeerScoreThresholds(), score_enabled=False, heartbeat_every=R)
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)
    step = make_gossipsub_phase_step(cfg, net, R)
    state = GossipSubState.init(net, M, cfg, seed=3)
    scan = driver.make_scan(step, heartbeat_every=R, rounds_per_phase=R,
                            static_heartbeat=True)
    rng = np.random.default_rng(0)
    xs = (jnp.asarray(rng.integers(0, N, (2 * R, P)).astype(np.int32)),
          jnp.zeros((2 * R, P), jnp.int32), jnp.ones((2 * R, P), bool))
    return scan, state, xs


def test_the_set_up_leaves_its_spans_where_the_work_happens(recorder):
    scan, state, xs = _toy()
    sha = lambda: hashlib.sha256(
        scan.lower(state, *xs).as_text().encode()).hexdigest()
    state = scan(state, *xs)
    state = scan(state, *xs)
    jax.block_until_ready(state)
    got = spans.recorded()
    (build,) = _named(got, "setup.net_build")
    (plan,) = _named(got, "setup.net_build.plan")
    (planes,) = _named(got, "setup.net_build.planes")
    assert plan.parent == planes.parent == build.id
    assert plan.end_ns <= planes.start_ns
    assert ((plan.end_ns - plan.start_ns) + (planes.end_ns - planes.start_ns)
            <= build.end_ns - build.start_ns)
    assert len(_named(got, "setup.step_build")) == 1
    assert len(_named(got, "setup.state_init")) == 1
    # two calls, one trace, lowering and compile of the window, after the
    # set-up's spans and under none of them
    ours = [s for s in got
            if stages.window_name() in s.attrs.get("fun_name", "")]
    assert [s.name for s in ours] == list(COMPILE)
    assert all(s.parent is None for s in ours)
    assert ours[0].start_ns >= _named(got, "setup.state_init")[0].end_ns
    assert scan._cache_size() == 1

    # no traced function changed: the window lowers to one text whatever
    # the recorder holds
    full = sha()
    spans.clear()
    assert sha() == full


def test_a_second_window_is_a_second_program(recorder):
    scan, state, xs = _toy()
    state = scan(state, *xs)
    again, state2, _ = _toy()
    state2 = again(state2, *xs)
    state2 = again(state2, *xs)
    got = spans.recorded()
    assert len([s for s in _named(got, "compile.backend")
                if stages.window_name() in s.attrs["fun_name"]]) == 2
    assert len(_named(got, "setup.net_build")) == 2


def test_a_program_jax_cannot_name_keeps_no_trace(recorder):
    x = jnp.arange(8.0)
    spans.clear()
    jax.jit(functools.partial(jnp.multiply, 3.0))(x)
    assert [s.name for s in spans.recorded()] == ["compile.lower",
                                                  "compile.backend"]


def _sources():
    for root, _, files in os.walk(PKG):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    yield os.path.relpath(path, PKG), f.read()


def test_one_recorder():
    """No module but ``perf/spans.py`` opens a host span's annotation (it
    is spelled in ``perf/stages.py``, with the other names, and called from
    ``spans.py`` alone), every span opened anywhere is one of ``SPANS``,
    every name of ``SPANS`` that is not jax's is opened somewhere, and the
    clock pairs the spans replaced are gone."""
    opened = set()
    for path, src in _sources():
        if path != os.path.join("perf", "stages.py"):
            assert "TraceAnnotation" not in src, path
        if path != os.path.join("perf", "spans.py"):
            assert "host_scope" not in src or path == os.path.join(
                "perf", "stages.py"), path
            assert "perf_counter_ns" not in src, path
        opened.update(re.findall(r"\b_?span\(\s*\"([a-z_.]+)\"", src))
    assert opened <= set(spans.SPANS)
    assert opened == {s for s in spans.SPANS if not s.startswith("compile.")}
    by_path = dict(_sources())
    sup = by_path[os.path.join("serve", "supervisor.py")]
    assert "t_seg" not in sup and sup.count("perf_counter") == 2   # the run's
    run = by_path[os.path.join("ensemble", "runner.py")]
    body = run[run.index("class WindowRunner"):run.index("def run_window")]
    assert "perf_counter" not in body
    with open(os.path.join(PKG, "perf", "spans.py"), encoding="utf-8") as f:
        assert len(f.read().splitlines()) < 200
