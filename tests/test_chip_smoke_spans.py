"""``chip_smoke.py``'s served line carries the served loop's host spans."""

import json

import jax
import pytest

import chip_smoke as cs


@pytest.fixture
def prng_restored():
    old = str(jax.config.jax_default_prng_impl)
    yield
    jax.config.update("jax_default_prng_impl", old)


def test_the_served_line_prints_the_host_spans(capsys, tmp_path,
                                               prng_restored):
    cs.phase_served(64, 4, 6, str(tmp_path), jax.devices()[:1])
    (line,) = [json.loads(x) for x in capsys.readouterr().out.splitlines()
               if json.loads(x).get("phase") == "served"]
    host = line["host_ms_uninterrupted"]
    # six segments, each dispatched and probed once, five stacked ahead of
    # their segment and the first before its own, two rolling checkpoints
    assert host["segment"]["n"] == host["dispatch"]["n"] == 6
    assert host["probe_readback"]["n"] == 6 and host["stack_args"]["n"] == 6
    assert host["checkpoint_save"]["n"] == 2
    assert host["heartbeat_write"]["n"] == 8      # start, 6 segments, done
    assert "report_row" in host and "restore" not in host
    inside = sum(host[k]["total"] for k in (
        "dispatch", "probe_readback", "checkpoint_save"))
    assert inside <= host["segment"]["total"] + 0.01
    assert host["outside_segments"]["total"] >= 0.0
    assert all(v["median"] <= v["total"] for k, v in host.items()
               if k != "outside_segments")
