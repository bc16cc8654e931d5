"""Multi-device semantics, each in a subprocess with 8 host devices (the
main test process keeps 1 device per the dry-run isolation rule)."""
import pytest

from conftest import run_multidev


def test_offload_gradients_all_policies():
    out = run_multidev("offload_grads.py")
    assert out.count("OK") >= 5


def test_offload_fp8_oracle():
    out = run_multidev("offload_fp8.py")
    assert "fp8 oracle test OK" in out


def test_moe_mesh_ep_and_tp():
    out = run_multidev("moe_mesh.py")
    assert "EP shard_map MoE == dense ref OK" in out
    assert "TP-in-expert MoE == dense ref OK" in out
    assert "MoE gradients == dense ref OK" in out


def test_ring_and_compressed_collectives():
    out = run_multidev("collectives.py")
    assert "ring_all_reduce == sum OK" in out
    assert "compressed OK" in out


def test_pipeline_equals_sequential():
    out = run_multidev("pipeline.py", devices=4)
    assert "pipeline == sequential OK" in out


def test_sharded_train_step_equivalence():
    out = run_multidev("sharded_train_equiv.py", timeout=900)
    assert "single-device oracle OK" in out
    assert "traces its step once OK" in out
