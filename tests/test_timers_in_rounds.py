"""Time's two units, where they are settled (ROADMAP queue 3 item 4):
``core.tick`` counts delivery ROUNDS, so a duration that is compared
against it is built in rounds: heartbeats times ``heartbeat_every``. P3's
activation and window and the gater's quiet period, in engine and oracle
alike; at ``heartbeat_every`` 1 every one of them is where it was."""

from __future__ import annotations

import numpy as np
import pytest

from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerGaterParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import GossipSubConfig
from go_libp2p_pubsub_tpu.oracle.score import OracleScore
from go_libp2p_pubsub_tpu.score.engine import TopicParamsArrays
from go_libp2p_pubsub_tpu.score.params import ScoreParams


def _params(**topic):
    tp = TopicScoreParams(mesh_message_deliveries_activation=10.0,
                          mesh_message_deliveries_window=2.0, **topic)
    return PeerScoreParams(topics={0: tp}, skip_app_specific=True)


@pytest.mark.parametrize("he,activation,window", [
    (1, 10, 1),          # where they were: 10 heartbeats, 2 less one
    (8, 80, 15),         # 10 s and 2 s of 8 rounds a second, less one
    (4, 40, 7),
])
def test_p3_activation_and_window_are_built_in_rounds(he, activation, window):
    tpa = TopicParamsArrays.build(_params(), 1, 1.0, he)
    assert tpa.activation_ticks.tolist() == [activation]
    assert tpa.window_rounds.tolist() == [window]
    # the default argument is heartbeat_every 1: every per-round build
    assert TopicParamsArrays.build(_params(), 1).activation_ticks[0] == 10
    assert TopicParamsArrays.build(_params(), 1).window_rounds[0] == 1
    # a window under one heartbeat stays "the same round only"
    short = PeerScoreParams(topics={0: TopicScoreParams()},
                            skip_app_specific=True)
    assert TopicParamsArrays.build(short, 1, 1.0, he).window_rounds[0] == 0


def test_a_topic_without_p3_keeps_its_unread_activation_row():
    """With P3 and P3b both weightless nothing reads the activation latch,
    and the row keeps its heartbeat count: the honest benchmark cells'
    windows lower to the text they had (PERF.md section 6, PR 35)."""
    dead = _params(mesh_message_deliveries_weight=0.0,
                   mesh_failure_penalty_weight=0.0)
    assert TopicParamsArrays.build(dead, 1, 1.0, 8).activation_ticks[0] == 10
    sticky = _params(mesh_message_deliveries_weight=0.0)
    assert TopicParamsArrays.build(sticky, 1, 1.0, 8).activation_ticks[0] == 80


@pytest.mark.parametrize("he", [1, 8])
def test_the_lifted_plane_carries_the_same_rows(he):
    cfg = GossipSubConfig.build(GossipSubParams(), PeerScoreThresholds(),
                                score_enabled=True, heartbeat_every=he)
    plane = ScoreParams.from_config(cfg, _params(), 1)
    tpa = TopicParamsArrays.build(_params(), 1, 1.0, he)
    assert np.asarray(plane.activation_ticks).tolist() == tpa.activation_ticks.tolist()
    assert np.asarray(plane.window_rounds).tolist() == tpa.window_rounds.tolist()


@pytest.mark.parametrize("he,quiet", [(1, 60), (8, 480)])
def test_the_gaters_quiet_period_is_read_in_rounds(he, quiet):
    cfg = GossipSubConfig.build(GossipSubParams(), PeerScoreThresholds(),
                                score_enabled=True, heartbeat_every=he,
                                gater_params=PeerGaterParams())
    assert cfg.gater_quiet_ticks == 60 and cfg.gater_quiet_rounds == quiet


@pytest.mark.parametrize("he", [1, 8])
def test_the_oracle_activates_p3_on_the_same_clock(he):
    o = OracleScore(_params(), heartbeat_every=he)
    o.graft(3, 0, tick=0)
    o.refresh(10 * he)                    # not OVER the activation yet
    assert not o.stats[(3, 0)].mmd_active
    o.refresh(10 * he + 1)
    assert o.stats[(3, 0)].mmd_active
    assert OracleScore(_params()).heartbeat_every == 1
