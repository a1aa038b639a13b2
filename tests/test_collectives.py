"""SOAR collective schedule: static program properties + multi-device
equivalence (subprocess: forced host device count must precede jax init)."""
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.collectives import build_program, chip_level_tree, fleet_tree, plan
from repro.core.reduce import all_blue, all_red, messages_up, phi
from repro.core.soar import soar


def test_fleet_tree_structure():
    topo = fleet_tree(n_pods=2, racks_per_pod=4, chips_per_rack=4)
    assert topo.n_devices == 32
    assert topo.load.sum() == 32
    assert topo.tree.height == 2  # spine -> pod -> rack


def test_program_message_count_matches_phi_simulator():
    topo = chip_level_tree(2, 2, 2)
    for k in (0, 1, 3):
        blue, prog = plan(topo, k)
        msgs = messages_up(topo.tree, topo.load, blue)
        assert prog.total_network_messages == msgs.sum()
        assert prog.utilization == pytest.approx(
            phi(topo.tree, topo.load, blue))


def test_soar_placement_on_fleet_beats_baselines():
    topo = fleet_tree(n_pods=2, racks_per_pod=8, chips_per_rack=8)
    res = soar(topo.tree, topo.load, 4)
    for s in ("top", "max", "level", "random"):
        _, prog = plan(topo, 4, strategy=s)
        assert res.cost <= prog.utilization + 1e-9


def test_heterogeneous_rates_prefer_below_dcn_aggregation():
    """With expensive DCN links, SOAR should aggregate at/below pods."""
    topo = fleet_tree(n_pods=2, racks_per_pod=4, chips_per_rack=8)
    res = soar(topo.tree, topo.load, 2)
    t = topo.tree
    picked = np.nonzero(res.blue)[0]
    assert len(picked) == 2
    # both picks are pod switches (depth 1): collapse 32 msgs before the DCN
    assert all(t.depth[v] == 1 for v in picked)


def test_all_blue_program_sends_one_message_per_edge():
    topo = chip_level_tree(2, 2, 2)
    prog = build_program(topo, all_blue(topo.tree))
    assert prog.total_network_messages == topo.tree.n  # one per up-edge


@pytest.mark.slow
def test_tree_allreduce_equals_psum_subprocess():
    script = pathlib.Path(__file__).parent / "helpers" / "collective_check.py"
    env = {"PYTHONPATH": "src"}
    import os
    env = {**os.environ, "PYTHONPATH": "src"}
    out = subprocess.run([sys.executable, str(script)], cwd=str(
        pathlib.Path(__file__).parent.parent), env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "COLLECTIVE_CHECK_OK" in out.stdout


# ---------------------------------------------------------------------------
# executor against a numpy replay of the program (one subprocess, 8 fake
# devices: tests/helpers/executor_replay_check.py)
# ---------------------------------------------------------------------------

_SWEEP = [f"k{k}-{s}" for k in (0, 1, 2, 4, 15)
          for s in ("soar", "top", "max", "random")]
REPLAY_CASES = (_SWEEP + ["all_red", "all_blue", "failed_devices"]
                + [f"spill{i}" for i in range(8)]
                + ["degraded_root", "spill_rack", "spill_two_racks",
                   "spill_pod_and_rack"])


@pytest.fixture(scope="module")
def replay_run():
    """The helper's output: {"CASE": {name: line}, "FEATURE": {name: n},
    "SCATTERS": n}."""
    import os
    root = pathlib.Path(__file__).parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tests" / "helpers"
                             / "executor_replay_check.py")],
        cwd=str(root), env={**os.environ, "PYTHONPATH": "src"},
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    got = {"CASE": {}, "FEATURE": {}, "SCATTERS": None}
    for line in out.stdout.splitlines():
        head, _, rest = line.partition(" ")
        if head == "CASE":
            name, _, verdict = rest.partition(" ")
            got["CASE"][name] = verdict
        elif head == "FEATURE":
            name, n = rest.split()
            got["FEATURE"][name] = int(n)
        elif head == "SCATTERS":
            got["SCATTERS"] = int(rest)
    return got


@pytest.mark.parametrize("case", REPLAY_CASES)
def test_executor_matches_numpy_replay_bitwise(replay_run, case):
    """tree_allreduce (flat) and reduce_local (shaped values) equal a plain
    float32 replay of the program byte for byte, and every delivery lies
    inside the slot buffer (off + cnt <= n_slots)."""
    assert set(replay_run["CASE"]) == set(REPLAY_CASES)
    assert replay_run["CASE"][case] == "ok", replay_run["CASE"][case]


@pytest.mark.parametrize("feature", ["slab_gt_1", "FoldOp", "CompactOp",
                                     "root_count_gt_1"])
def test_replay_cases_cover_degraded_ops(replay_run, feature):
    assert replay_run["FEATURE"][feature] > 0


def test_cell_reduce_lowers_without_scatter(replay_run):
    """The gradient-sync cell's program (dp_fleet(4), k = 2) lowered inside
    shard_map on 4 devices: no slot update is a scatter."""
    assert replay_run["SCATTERS"] == 0


# ---------------------------------------------------------------------------
# fail_devices state-corruption regressions
# ---------------------------------------------------------------------------

def test_fail_devices_duplicate_ids_release_load_once():
    """A duplicated id in `dead` must fail the device once, not drain its
    leaf's load twice."""
    from repro.collectives import fail_devices
    topo = fleet_tree(2, 2, 2)
    t2 = fail_devices(topo, [3, 3, 3])
    assert t2.load.sum() == topo.load.sum() - 1
    assert t2.device_leaf[3] == -1
    assert (t2.load >= 0).all()


def test_fail_devices_already_dead_raises_and_preserves_last_switch():
    """Failing an already-failed device used to index load[-1] and silently
    drain the *last* switch's load; it must raise instead."""
    from repro.collectives import fail_devices
    topo = fleet_tree(2, 2, 2)
    once = fail_devices(topo, [0])
    last_load = once.load[-1]
    with pytest.raises(ValueError):
        fail_devices(once, [0])
    assert once.load[-1] == last_load          # untouched by the rejected call
    with pytest.raises(ValueError):
        fail_devices(topo, [topo.n_devices])   # out-of-range id


def test_plan_batch_rejects_mismatched_avail_lengths():
    """plan_batch used to zip-truncate silently when len(avails) !=
    len(topos); now it is a hard error."""
    from repro.collectives.schedule import plan_batch
    topo = fleet_tree(2, 2, 2)
    with pytest.raises(ValueError):
        plan_batch([topo, topo], 2, [None])
    with pytest.raises(ValueError):
        plan_batch([topo], 2, [None, None], strategy="top")
