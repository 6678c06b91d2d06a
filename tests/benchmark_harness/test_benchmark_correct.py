"""``correct`` has to be able to fail. The controls of "How correct is
decided" at a size a test run can hold (lossy links through the program's
own chaos path; the program built with gossip switched off, or with a mesh
kept under D_lo; the score plane in bfloat16), and the rest of a run driven
with the timed path broken underneath by each fault of
``benchmark/harness/faults.py``: a step that returns its state unchanged,
half of the batch left out, an answer altered where it is produced, the
first-delivery counters dropped. (One chip: there is no exchange between
chips to leave out.)"""

import time

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import faults, graphs
from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()
reference = mf.load_plugin("references", "gossipsub")


SCORED = "random-100k.stepped"


def _one_cell_per_config():
    seen = {}
    for w in MANIFEST["workloads"]:
        seen.setdefault(w["config"], w["name"])
    return sorted(seen.values())


CELLS_ONE_MIX_EACH = _one_cell_per_config()


def toy(cell_name, seed, segments, n=256, **overrides):
    cell = mf.find_cell(MANIFEST, cell_name)
    out = bench_run.measure(
        MANIFEST, cell, seed, 1e9, False, jax.devices()[:1],
        time.perf_counter(),
        overrides=dict(overrides, n_peers=n, max_segments=segments))
    return out["result"]


def failed_numbers(result):
    return {x["name"] for x in result["compared"] if x["value"] > x["limit"]}


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "tick_gap"),
    ("half_batch", "msgs_mismatch"),
    ("answer_altered", "have_mismatch"),
    ("fmd_dropped", "fmd_short"),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by):
    result = toy(SCORED, 21, 6, fault=fault)
    assert not result["correct"]
    assert caught_by in failed_numbers(result)


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_control_bfloat16_scores_fail(seed):
    sound = toy(SCORED, seed, 12)
    assert sound["correct"], sound["compared"]
    control = toy(SCORED, seed, 12, control={"score_dtype": "bfloat16"})
    assert not control["correct"]
    assert failed_numbers(control) == {"score_gap"}


@pytest.mark.parametrize("cell", CELLS_ONE_MIX_EACH)
def test_control_lossy_links_fail(cell):
    control = toy(cell, 41, 2, n=1024, control={"chaos_loss_rate": 0.02})
    assert not control["correct"]
    assert "push_gap_share" in failed_numbers(control)


@pytest.mark.parametrize("params,caught_by", [
    ({"D_lazy": 0, "gossip_factor": 0.0}, "ihave_mismatch"),
    ({"D": 3, "D_lo": 2, "D_score": 2, "D_out": 1}, "mesh_degree_out"),
], ids=["gossip_off", "mesh_kept_under_D_lo"])
@pytest.mark.parametrize("cell", CELLS_ONE_MIX_EACH)
def test_control_other_mesh_parameters_fail(cell, params, caught_by):
    control = toy(cell, 51, 2, n=512,
                  control={"program_mesh_params": params})
    assert not control["correct"]
    assert caught_by in failed_numbers(control)


@pytest.mark.parametrize("name", ["gossipsub", "gossipsub_subnets",
                                  "gossipsub_sybil", "gossipsub_churn"])
def test_over_d_hi_allows_the_outbound_top_up_and_nothing_else(name):
    """``mesh_degree_out``'s upper half, in every copy of the reference: the
    heartbeat prunes to D and THEN grafts outbound peers up to D_out
    (gossipsub.go:1451-1476), so a mesh stands over D_hi only by outbound
    members, and by no more than D_out of them in all."""
    over = mf.load_plugin("references", name).over_d_hi
    mp = {"D_hi": 4, "D_out": 2}
    k = 8
    outbound = np.zeros((1, k), bool)
    outbound[0, :4] = True                    # slots 0-3 dialled, 4-7 accepted

    def mesh(*slots):
        m = np.zeros((1, 1, k), bool)
        m[0, 0, list(slots)] = True
        return m

    cases = [
        (mesh(4, 5, 6, 7), False),            # D_hi, no outbound: not over
        (mesh(0, 4, 5, 6, 7), False),         # topped up by one outbound
        (mesh(0, 1, 4, 5, 6, 7), False),      # ... by D_out of them
        (mesh(0, 1, 2, 4, 5, 6, 7), True),    # D_hi + D_out + 1
        (mesh(0, 1, 2, 5, 6), True),          # D_hi + 1 with D_out outbound
                                              # in it already, and one more
        (mesh(0, 1, 2, 3, 4), True),          # over by one, four outbound
        (mesh(3, 4, 5, 6, 7), False),         # over by one, its one outbound
        (mesh(0, 1, 4, 5, 6), False),         # D_hi + 1, D_out outbound
    ]
    for planted, breach in cases:
        assert bool(over(planted, outbound, mp)[0, 0]) == breach, planted
    # an inbound member over D_hi is no top-up, whatever the outbound count
    wide = np.zeros((1, 12), bool)
    m = np.zeros((1, 1, 12), bool)
    m[0, 0, :5] = True                        # five inbound members
    assert over(m, wide, mp)[0, 0]
    both = np.concatenate([mesh(0, 4, 5, 6, 7), mesh(0, 1, 2, 5, 6)], axis=1)
    assert over(both, outbound, mp).tolist() == [[False, True]]


def _refused_graft_with_an_old_time_in_mesh(st):
    """What the phase's head leaves when it refuses a GRAFT from a
    neighbour that was in the mesh before: the PRUNE answer in the
    outbox, and the time in mesh of the earlier membership untouched
    (``test_benchmark_subnets.py``'s case, for ``references/gossipsub.py``)."""
    import jax.numpy as jnp

    config = mf.load_config(MANIFEST, SCORED.split(".")[0])
    g = graphs.build_graph(config["graph"], st.mesh.shape[0])
    free = (g["nbr_ok"][9] & ~np.asarray(st.mesh)[9, 0]
            & ~np.asarray(st.prune_out)[9, 0])
    k = int(np.flatnonzero(free)[0])
    score = st.score.replace(
        mesh_time=jnp.asarray(st.score.mesh_time).at[9, 0, k].set(4),
        graft_tick=jnp.asarray(st.score.graft_tick).at[9, 0, k].set(10))
    return st.replace(score=score,
                      prune_out=jnp.asarray(st.prune_out).at[9, 0, k].set(True))


def test_a_refused_graft_is_no_mesh_member_of_the_score_refresh(monkeypatch):
    """The repair ``eth2-100k``'s copy found (3 of 8 seeds read ``score_gap``
    0.029 on the chip) in the first reference too: a PRUNE-outbox edge is a
    member at the score refresh only if the refresh just wrote its time."""
    def fault(window, state, po, pt, pv):
        return _refused_graft_with_an_old_time_in_mesh(
            window(state, po, pt, pv))

    monkeypatch.setitem(faults.FAULTS, "planted", fault)
    result = toy(SCORED, 65, 8, fault="planted")
    (gap,) = [x["value"] for x in result["compared"] if x["name"] == "score_gap"]
    assert gap == 0.0
    assert result["correct"], result["compared"]


def test_reference_pieces():
    words = np.array([[0b101, 1 << 31]], np.uint32)
    bits = reference.unpack_bits(words, 64)
    assert bits.shape == (1, 64) and list(np.flatnonzero(bits[0])) == [0, 2, 63]
    # ring allocator: 3 rounds x 2 publishes into 4 slots
    origin = np.array([[10, 11], [12, 13], [14, 15]])
    topic = np.zeros((3, 2), int)
    got = reference.allocate(5, origin, topic, 4)
    # publish g = round*2 + j -> slot g % 4: round 7 overwrites round 5
    assert list(got["origin"]) == [12, 13, 14, 15]
    assert list(got["birth"]) == [6, 6, 7, 7]
    a = np.array([[1.0, 2.0]], np.float32)
    assert reference.score_gap(a, a) == 0.0
    assert reference.score_gap(a * 1.01, a) == pytest.approx(0.01, rel=1e-3)
    bits = np.zeros((3, 70), bool)
    bits[1, [0, 33, 69]] = True
    assert np.array_equal(
        reference.unpack_bits(reference.pack_bits(bits), 70), bits)
