"""A configuration file describes the program's model: the published keys
map as they always have, a ``program`` object describes any other family
the program builds, and the reference module beside the file may bring
the configuration's own counts of operations."""
import dataclasses
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import common, flops, runner, spec, train_cell  # noqa: E402

# the mapping of the two files as the harness made it before the file
# could carry a ``program`` object, every field given
FROZEN = {
    "smollm-135m": dict(
        name="smollm-135m", family="dense", num_layers=30, d_model=576,
        num_heads=9, num_kv_heads=3, head_dim=64, d_ff=1536,
        vocab_size=49152, attention="full", window=4096,
        rope_theta=10000.0, act="silu", norm="rmsnorm",
        tie_embeddings=True, dtype="bfloat16"),
    "h2o-danube-1.8b": dict(
        name="h2o-danube-1.8b", family="dense", num_layers=24,
        d_model=2560, num_heads=32, num_kv_heads=8, head_dim=80, d_ff=6912,
        vocab_size=32000, attention="swa", window=4096,
        rope_theta=10000.0, act="silu", norm="rmsnorm",
        tie_embeddings=False, dtype="bfloat16"),
}
# train_step_flops(cfg, 16, 2048) and (cfg, 4, 4096) as counted then
FROZEN_FLOPS = {"smollm-135m": (33401063079936.0, 20179455049728.0),
                "h2o-danube-1.8b": (368649122611200.0, 196694067118080.0)}


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_published_files_map_as_before(name):
    from repro.configs.base import ModelConfig
    cfg = spec.config(name)
    assert "program" not in cfg
    got = dataclasses.asdict(common.model_config(cfg))
    assert got == dataclasses.asdict(ModelConfig(**FROZEN[name]))
    count = spec.count(cfg, "train_step_flops")
    assert count is flops.train_step_flops
    assert (count(cfg, 16, 2048), count(cfg, 4, 4096)) == FROZEN_FLOPS[name]


def _zamba2_file():
    """The program's zamba2-2.7b preset at its CPU size, as a
    configuration file: published keys and a ``program`` object."""
    from repro.configs import ARCHS
    m = ARCHS["zamba2-2.7b"].reduced()
    return m, {
        "name": m.name, "reference": "llama_dense",
        "hidden_act": "gelu", "hidden_size": m.d_model,
        "intermediate_size": m.d_ff,
        "num_attention_heads": m.num_heads,
        "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
        "num_hidden_layers": m.num_layers, "vocab_size": m.vocab_size,
        "rope_theta": m.rope_theta, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16",
        "program": {"family": "hybrid", "act": "gelu",
                    "ssm_state": m.ssm_state,
                    "ssm_head_dim": m.ssm_head_dim,
                    "ssm_chunk": m.ssm_chunk,
                    "hybrid_attn_every": m.hybrid_attn_every,
                    "window": m.window, "vocab_pad_to": m.vocab_pad_to,
                    "frontend_tokens": m.frontend_tokens,
                    "sub_quadratic": True}}


def test_program_object_builds_a_hybrid_and_steps_it():
    import jax
    preset, cfg = _zamba2_file()
    assert common.model_config(cfg) == preset
    mix = dict(spec.traffic("train.host"), batch=2, seq=32)
    model, tc, step, state, sharding = train_cell.build(cfg, mix, 2 ** 33 + 5)
    assert model.cfg.family == "hybrid" and sharding is None
    feed = train_cell.Feed(2 ** 33 + 5, mix, cfg["vocab_size"])
    losses = []
    for _ in range(2):
        state, m = step(state, next(feed))
        losses.append(float(m["loss"]))
    assert int(jax.device_get(state["step"])) == 2
    assert all(0 < x < 100 for x in losses), losses
    # harness/flops.py counts a dense decoder: a hybrid brings its own
    with pytest.raises(spec.SpecError, match="train_step_flops"):
        spec.count(cfg, "train_step_flops")


@pytest.mark.parametrize("program, match", [
    ({"num_expert": 8}, "not fields"),
    ({"d_model": 640}, "contradict"),
    ({"num_layers": 31}, "contradict"),
    ({"num_heads": 8}, "contradict"),
    ({"vocab_size": 32000}, "contradict"),
    ({"attention": "full"}, "contradict"),
])
def test_program_object_is_refused(program, match):
    name = "h2o-danube-1.8b" if "attention" in program else "smollm-135m"
    cfg = dict(spec.config(name), program=program)
    with pytest.raises(spec.SpecError, match=match):
        common.model_config(cfg)


def test_act_other_than_silu_needs_the_program_object():
    cfg = dict(spec.config("smollm-135m"), hidden_act="gelu")
    with pytest.raises(spec.SpecError, match="hidden_act"):
        common.model_config(cfg)
    cfg["program"] = {"act": "gelu"}
    assert common.model_config(cfg).act == "gelu"


@pytest.mark.parametrize("program", [{"act": "gelu"}, {"family": "dense"},
                                     {"attention": "swa", "window": 512}])
@pytest.mark.parametrize("name", spec.COUNTS)
def test_a_program_object_needs_its_own_counts(program, name):
    """harness/flops.py counts the SwiGLU decoder of the published keys:
    a file whose program object may change that (a gelu MLP of two
    matmuls, a window the published keys do not state) is refused it."""
    cfg = dict(spec.config("smollm-135m"), hidden_act=program.get(
        "act", "silu"), program=program)
    common.model_config(cfg)
    with pytest.raises(spec.SpecError, match=name):
        spec.count(cfg, name)


TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)
BENCH = {"end_to_end": [
    {"name": "setup_s", "unit": "s"},
    {"name": "train_tokens_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": u} for n, u in (
        ("mfu.train", "%"), ("tier.wire_bytes_per_token", "B/token"),
        ("step.attention_core_share", "%"))]}


def test_reference_counts_are_the_ones_the_cell_uses(monkeypatch):
    """A traced run's ``mfu.train`` is the reference module's FLOPs of the
    traced steps, and its tier bytes per token the runtime's executed
    bytes of those steps."""
    real = spec.reference
    calls = []

    def own_flops(cfg, batch, seq):
        calls.append((batch, seq))
        return 4.0e9 * batch * seq

    def reference(cfg, base=spec.HERE):
        return types.SimpleNamespace(**vars(real(cfg, base)),
                                     train_step_flops=own_flops)

    monkeypatch.setattr(spec, "reference", reference)
    cfg = dict(spec.config("smollm-135m"), **TINY)
    mix = dict(spec.traffic("train.host"), batch=4, seq=64)
    cell = {"name": "t", "chips": 1, "config_spec": cfg, "traffic_spec": mix}
    out = runner.run("t", 2 ** 32 + 9, 2.0, True, require_tpu=False,
                     cell=cell, limits=spec.limits("smollm-135m.train.host"),
                     bench=BENCH)
    assert out["correct"], out["checks"]
    assert calls == [(4, 64)]
    traced = mix["traced_steps"]
    peak = runner.peaks()["TPU v5 lite"]["bf16_flops_per_s"]
    got = out["metrics"]
    assert got["mfu.train"]["value"] == pytest.approx(
        100 * traced * 4.0e9 * 4 * 64 / (peak * out["device"]["window_s"]))
    tier = out["log"]["tier_traffic"]
    per_token = (tier["stash"]["per_step"] + tier["fetch"]["per_step"]) \
        / (4 * 64)
    assert per_token > 0
    assert got["tier.wire_bytes_per_token"]["value"] == \
        pytest.approx(per_token)
    # the CPU's trace has no device plane, so nothing carries a scope
    assert "step.attention_core_share" not in got
