"""chip_smoke.py's control flow, guarded by tier-1: every phase runs as a
function at toy size on the CPU (the four default phases on one device,
the ``--chips 4`` comparison on four virtual devices), the script refuses
to pass without a TPU, and a failed check fails the run. Plus the
fallbacks this path must never grow back: N-halving, an "error" line with
exit 0, an off-chip bench, a silent one-device placement, a cache
directory set over the standard variable.

Nothing here is a chip run: sizes are toy, the backend is the CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

from go_libp2p_pubsub_tpu import compile_cache  # noqa: E402
from go_libp2p_pubsub_tpu.perf import sweep  # noqa: E402


@pytest.fixture
def prng_restored():
    old = str(jax.config.jax_default_prng_impl)
    yield
    jax.config.update("jax_default_prng_impl", old)


def _lines(capsys) -> dict:
    return {d["phase"]: d for d in
            map(json.loads, capsys.readouterr().out.splitlines())}


# ---------------------------------------------------------------------------
# the phases, at toy size


def test_engine_phase(capsys, prng_restored):
    cs.phase_engine(2048, 160, 3, inv_n=2048, inv_rounds=160,
                    devices=jax.devices()[:1])
    out = _lines(capsys)
    eng, inv = out["engine"], out["engine.invariants"]
    assert eng["tick"] == 160 * (1 + 3 + 2) and eng["scan_compiles"] == 1
    assert len(eng["segment_seconds"]) == 3 and eng["live_messages"] == 64
    assert sum(eng["mesh_degree_histogram"].values()) == 2048
    # the window spans ticks 104..128, where a lazy-clear period of
    # backoff_clear_ticks alone (not lcm with heartbeat_every = 8) would
    # flag every pruned edge (oracle/invariants.py backoff-clears)
    assert inv["violations"] == 0 and inv["checks"] == 5
    assert inv["evaluations"] == 5 * inv["properties"]


def test_engine_check_catches_a_wrapped_index(prng_restored):
    """The doctored check: a holder on the far side of the ring (what a
    wrapped index or a wrong gather produces) breaks the causality
    bound."""
    jax.config.update("jax_default_prng_impl", cs.BENCH_PRNG)
    n, r = 256, cs.BENCH_R
    cell = sweep.bench_cell(n, cs.BENCH_M, heartbeat_every=r,
                            rounds_per_phase=r, devices=jax.devices()[:1])
    scan, _ = sweep.make_bench_scan(cell.step, r, r)
    st = scan(cell.state, *(jnp.asarray(a) for a in sweep.bench_schedule(
        n, cell.n_topics, cell.honest, 32)))
    cs.check_engine_state(st, n, 32)
    newest = int(np.argmax(np.asarray(st.core.msgs.birth)))
    far = (int(st.core.msgs.origin[newest]) + n // 2) % n
    have = st.core.dlv.have.at[far, newest // 32].set(
        jnp.uint32(1 << (newest % 32)) | st.core.dlv.have[far, newest // 32])
    bad = st.replace(core=st.core.replace(dlv=st.core.dlv.replace(have=have)))
    with pytest.raises(cs.SmokeFailure, match="ring positions from origin"):
        cs.check_engine_state(bad, n, 32)
    with pytest.raises(cs.SmokeFailure, match="rounds executed"):
        cs.check_engine_state(st, n, 40)


def test_floodsub_phase_two_devices(capsys):
    cs.phase_floodsub(2048, 64, jax.devices()[0], jax.devices()[1])
    out = _lines(capsys)["floodsub"]
    assert out["delivery_ratio"] == 1.0
    assert out["device"] != out["reference"]


def test_api_phase(capsys, tmp_path):
    cs.phase_api(20, str(tmp_path))
    out = capsys.readouterr().out.splitlines()
    assert [json.loads(line)["rounds_per_phase"] for line in out] == [1, 8]
    assert all(json.loads(line)["delivered"] == 60 for line in out)


def test_served_phase(capsys, tmp_path, prng_restored):
    cs.phase_served(64, 4, 6, str(tmp_path), jax.devices()[:1])
    out = _lines(capsys)["served"]
    assert out["resumed_from_dispatch"] == 12 and out["segments"] == 6
    assert out["window_compiles"] == {"L4": 1}


def test_sharded_phase_on_four_virtual_devices(capsys, prng_restored):
    cs.phase_sharded(2048, 160, jax.devices()[:4])
    out = _lines(capsys)["sharded"]
    assert out["equal_to_one_device"] and out["rows_per_shard"] == 512
    # the XLA:CPU partitioner's exact halo budget (the TPU compiler's
    # differs — chip_smoke.phase_sharded): one permute per band
    # direction per gather set, no all-gather
    assert out["permute_sets_per_phase"] == cs.BENCH_R + 1
    assert out["collectives"]["collective-permute"] == 16 * (cs.BENCH_R + 1)
    assert out["collectives"]["all-gather"] == 0
    assert out["widest_collective_rows"] <= cs.LATTICE_D


def test_sharded_rows_catches_a_collapsed_leaf():
    from go_libp2p_pubsub_tpu.parallel import make_mesh, shard_state

    devs = jax.devices()[:4]
    tree = {"a": jnp.zeros((64, 3)), "tick": jnp.int32(0)}
    assert cs.sharded_rows(shard_state(tree, make_mesh(devices=devs), 64),
                           64, devs) == 1
    with pytest.raises(cs.SmokeFailure, match="shards on"):
        cs.sharded_rows(jax.device_put(tree, devs[0]), 64, devs)


# ---------------------------------------------------------------------------
# the script: no TPU -> no result; a failed phase -> non-zero exit


def _run(args, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_prints_no_result_without_a_tpu():
    rc = _run(["chip_smoke.py"])
    assert rc.returncode != 0
    assert '"ok"' not in rc.stdout and "no TPU" in rc.stderr


_DOCTORED = """
import types, jax, chip_smoke as cs
fake = types.SimpleNamespace(platform="tpu", device_kind="fake v5e")
jax.devices = lambda *a: [fake]
noop = lambda *a, **k: None
cs.phase_engine = cs.phase_api = cs.phase_served = noop
cs.phase_floodsub = {floodsub}
raise SystemExit(cs.main([]))
"""


def test_failed_phase_gives_non_zero_exit_and_no_result():
    rc = _run(["-c", _DOCTORED.format(
        floodsub="lambda *a, **k: cs.require(False, 'doctored check')")])
    assert rc.returncode != 0 and "doctored check" in rc.stderr
    assert '"ok"' not in rc.stdout


def test_last_line_is_the_device_as_jax_reports_it():
    rc = _run(["-c", _DOCTORED.format(floodsub="noop")])
    assert rc.returncode == 0, rc.stderr[-400:]
    assert rc.stdout.splitlines()[-1] == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "fake v5e", '
        '"count": 1}}')


# ---------------------------------------------------------------------------
# the bench path fails loudly


def test_measure_rate_raises_instead_of_shrinking_n(monkeypatch):
    asked = []

    def oom(n_peers, *a, **k):
        asked.append(n_peers)
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory: 17.2G "
                           "exceeds the 15.7G of HBM")

    monkeypatch.setattr(sweep, "build_bench", oom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        sweep.measure_rate("default", 40_000, 64, 8, 8, 160)
    assert asked == [40_000]


def test_bench_exits_non_zero_on_failure():
    # seg_rounds below one lcm(he, r) group: the cell cannot run
    rc = _run(["bench.py"], BENCH_PLATFORM="cpu", BENCH_N="64",
              BENCH_ROUNDS="4")
    assert rc.returncode != 0 and "seg_rounds=0 < one lcm" in rc.stderr
    assert '"metric"' not in rc.stdout


def test_bench_fails_off_chip_unless_a_platform_is_named():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_PLATFORM"}
    rc = subprocess.run([sys.executable, "bench.py"], cwd=ROOT,
                        env={**env, "JAX_PLATFORMS": "cpu"},
                        capture_output=True, text=True, timeout=300)
    assert rc.returncode != 0 and "no TPU found" in rc.stderr
    assert rc.stdout.strip() == ""


def test_every_bench_line_names_the_device():
    fp = sweep.workload_fingerprint("default", 64, 64, 1, 1)
    dev = jax.devices()[0]
    assert (fp["platform"], fp["device_kind"], fp["n_devices"]) == (
        dev.platform, dev.device_kind, len(jax.devices()))
    assert sweep.select_platform("cpu")["platform"] == "cpu"
    with pytest.raises(RuntimeError, match="no TPU found"):
        sweep.select_platform(None)


def test_build_bench_raises_when_n_does_not_divide_the_devices():
    assert len(jax.devices()) == 8
    with pytest.raises(ValueError, match="does not divide over 8 devices"):
        sweep.build_bench(100, 64)
    with pytest.raises(ValueError, match="does not divide over 3 devices"):
        sweep.build_bench(64, 64, devices=jax.devices()[:3])
    st, *_ = sweep.build_bench(100, 64, devices=jax.devices()[:1])
    assert st.mesh.devices() == {jax.devices()[0]}
    st, *_ = sweep.build_bench(64, 64, devices=jax.devices()[:2])
    assert st.mesh.devices() == set(jax.devices()[:2])


# ---------------------------------------------------------------------------
# the compile cache is placed from outside


def test_cache_dir_is_left_alone_when_the_variable_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_persistent_cache("/another/dir")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_persistent_cache()
        assert (jax.config.jax_compilation_cache_dir
                == os.path.join(ROOT, ".jax_cache"))
        monkeypatch.setenv("JAX_NO_TEST_CACHE", "1")
        assert not compile_cache.enable_persistent_cache("/another/dir")
        assert (jax.config.jax_compilation_cache_dir
                == os.path.join(ROOT, ".jax_cache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
