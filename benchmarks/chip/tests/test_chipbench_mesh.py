"""A traffic file lays the cell's chips out as the program's mesh: the
pooled-HBM tier trains, and the engine serves, on a 2x2 mesh of virtual
CPU devices through the benchmark's own run, both correct and with
nothing traced or compiled in the window; a mesh that does not hold the
cell's chips is refused."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
sys.path[:0] = [HERE, SRC]

from harness import spec  # noqa: E402


def _mesh_run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "tests", "mesh_run.py"),
         *args], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    return out


def test_mcdla_on_a_2x2_mesh_is_correct():
    out = _mesh_run("train", "2x2", str(2 ** 31 + 77), "mcdla")
    assert out["log"]["traces_in_window"] == 0
    assert out["log"]["compiles_in_window"] == 0
    # the pool moved each layer's input to the other chips and back
    tier = out["log"]["tier_traffic"]
    assert tier["stash"]["per_step"] > 0
    assert tier["stash"]["wire_bytes"] == tier["fetch"]["wire_bytes"]


def test_serving_on_a_2x2_mesh_is_correct():
    out = _mesh_run("serve", "2x2", str(2 ** 31 + 77))
    assert out["log"]["checked_tokens"] > 0
    # the warm-up runs every program on the sharded page pool too
    assert out["log"]["traces_in_window"] == 0
    assert out["log"]["compiles_in_window"] == 0


def _cell(chips, mesh=None):
    mix = {"kind": "train"} if mesh is None else {"kind": "train",
                                                  "mesh": mesh}
    return {"name": "c", "chips": chips, "traffic_spec": mix}


@pytest.mark.parametrize("chips, mesh, want", [
    (1, None, ((1,), ("data",))),
    (4, None, ((4,), ("data",))),
    (4, {"shape": [2, 2], "axes": ["data", "model"]},
     ((2, 2), ("data", "model"))),
    (4, {"shape": [4, 1], "axes": ["data", "model"]},
     ((4, 1), ("data", "model"))),
])
def test_mesh_from_the_traffic_file(chips, mesh, want):
    assert spec.mesh(_cell(chips, mesh)) == want


@pytest.mark.parametrize("chips, mesh", [
    (4, {"shape": [2, 1], "axes": ["data", "model"]}),
    (1, {"shape": [2, 2], "axes": ["data", "model"]}),
    (4, {"shape": [2, 2], "axes": ["data"]}),
    (4, {"shape": [2, 2], "axes": ["data", "data"]}),
])
def test_mesh_that_does_not_fit_the_cell_is_refused(chips, mesh):
    with pytest.raises(spec.SpecError):
        spec.mesh(_cell(chips, mesh))
