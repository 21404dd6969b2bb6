"""A configuration with ``serve.mesh_shape`` on four CPU devices: each leaf
drawn in its served placement, the same bits as on one device, the
reference on the placed weights, and a tiny mesh cell run end to end
through ServeSystem. The checks run in one process of their own
(mesh_worker.py), which gives the CPU backend four devices before JAX
starts."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


@pytest.fixture(scope="module")
def mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "mesh_worker.py")],
                       env=env, cwd=CHECKOUT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


def test_mesh_draw_holds_each_chips_share_only(mesh):
    d = mesh["draw"]
    # tiny.json has 4 experts: one of each expert leaf and adapter factor
    # on each device, every other leaf whole on every device
    share = d["replicated_bytes"] + d["expert_bytes"] // 4
    assert d["device_bytes"] == {str(i): share for i in range(4)}
    for path, ps in d["specs"].items():
        if path.startswith("adapters."):
            assert ps == "PartitionSpec(None, None, 'data', None, None)"
        elif path.startswith("layers.moe.") and path != "layers.moe.router":
            assert ps == "PartitionSpec(None, 'data', None, None)"
        else:
            assert "data" not in ps, (path, ps)


def test_mesh_draw_is_bitwise_the_one_device_draw(mesh):
    assert all(mesh["draw"]["bitwise_equal"].values()), \
        mesh["draw"]["bitwise_equal"]
    assert len(mesh["draw"]["bitwise_equal"]) == 19
    assert mesh["draw"]["checksums_equal"]


def test_program_places_the_weights_where_the_draw_does(mesh):
    assert len(mesh["program_placement"]) == 13
    assert all(mesh["program_placement"].values()), mesh["program_placement"]


def test_reference_on_placed_weights_agrees_with_one_device(mesh):
    r = mesh["reference"]
    # the reference gathers each layer onto the first device and runs the
    # one-device program on it, so the only admissible difference is a
    # float32 sum taken in another order: a few ulps of the largest logit
    # per reduction of a few hundred terms
    assert r["max_abs_diff"] <= 64 * 1.2e-7 * r["max_abs_logit"]


def test_tiny_mesh_cell_is_correct_end_to_end(mesh):
    c = mesh["cell"]
    assert c["correct"], c["checks"]
    assert c["failed"] == 0 and c["attempted"] > 0
    assert c["checks"]["p98_logit_gap"]["value"] <= 0.05
