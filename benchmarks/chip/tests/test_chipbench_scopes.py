"""The scope reduction and its readers, on a small recorded trace and by
hand.

``data/train_host_scoped_slice.json`` is 4 ms of a traced window of
``smollm-135m.train.host`` on a TPU v5e, in the backward around the
tier's recompute, as ``harness.trace.load`` reduced it, with each
operation's scope path from the step's compiled text
(``harness.scopes.attach``).  Each scope's time is checked against a
brute-force count on a 100 ns grid."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import scopes as sc  # noqa: E402
from harness import spec  # noqa: E402
from harness.common import Traced  # noqa: E402
from harness.runner import Measurement  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
GRID = 100.0      # ns


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        t = json.load(f)
    return t, t["window"][0], t["window"][1]


@pytest.fixture(scope="module")
def scoped():
    return _load("train_host_scoped_slice.json")


def _self_mask(ops, lo, hi, keep):
    """Grid points, from ``lo`` to the end of the last operation that
    starts in [lo, hi), whose innermost running operation (the latest to
    start: operations nest) starts in [lo, hi) and is kept."""
    end = max(o[1] + o[2] for o in ops if lo <= o[1] < hi)
    ts = np.arange(lo, end, GRID) + GRID / 2
    start = np.full(ts.shape, -np.inf)
    owner = np.full(ts.shape, -1)
    for i, o in enumerate(ops):
        on = (ts >= o[1]) & (ts < o[1] + o[2]) & (o[1] >= start)
        start[on], owner[on] = o[1], i
    ok = np.array([lo <= o[1] < hi and keep(o) for o in ops] + [False])
    return ok[owner]


@pytest.mark.parametrize("name", ["tier.recompute", "attention_core", "mlp",
                                  "layers"])
def test_scope_time_counts_self_time_by_scope(scoped, name):
    t, lo, hi = scoped
    d = t["devices"]["0"]
    keep = lambda o: name in sc.scopes_of(d["scope"].get(o[0], ""))  # noqa
    brute = _self_mask(d["ops"], lo, hi, keep).sum() * GRID / 1e9
    got = sc.scope_seconds(t, lo, hi, lambda s: name in s)
    assert got > 0
    assert got == pytest.approx(brute, rel=1e-2, abs=2e-7)


def test_scope_split_partitions_the_busy_time(scoped):
    t, lo, hi = scoped
    split = sc.scope_split(t, lo, hi)
    assert set(split) == set(sc.SCOPES) | {sc.UNSCOPED}
    assert split["tier.recompute"] > 0
    started = [o for o in t["devices"]["0"]["ops"] if lo <= o[1] < hi]
    assert sum(split.values()) == pytest.approx(
        sum(o[3] for o in started) / 1e9)
    # the recompute's attention is the recompute's in the partition, and
    # counted in both by scope
    rec = sc.scope_seconds(t, lo, hi, lambda s: "tier.recompute" in s)
    assert split["tier.recompute"] == pytest.approx(rec)
    both = sc.scope_seconds(t, lo, hi, lambda s: {"tier.recompute",
                                                  "attention_core"} <= set(s))
    assert split["attention_core"] == pytest.approx(
        sc.scope_seconds(t, lo, hi, lambda s: "attention_core" in s) - both)


def test_scope_names_and_compiled_op_names():
    assert sc.scopes_of("jit(train_step)/transpose(jvp(layers))/while/body/"
                        "tier.recompute/jvp(attention_core)/dot_general") == [
        "train_step", "layers", "while", "body", "tier.recompute",
        "attention_core", "dot_general"]
    hlo = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  ROOT %exp.1 = f32[4]{0} exponential(%p), metadata={op_name='
        '"jit(f)/mlp/exp"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        '  ROOT %add.2 = f32[4]{0} add(%fusion.3, %a), metadata={op_name='
        '"jit(f)/loss/add"}',
        "}"])
    names = sc.hlo_op_names(hlo)
    assert names["fusion.3"] == "jit(f)/mlp/exp"
    assert names["add.2"] == "jit(f)/loss/add"
    trace = {"devices": {"0": {"ops": [["fusion.3", 0.0, 5.0, 5.0, 0],
                                       ["add.2", 5.0, 1.0, 1.0, 0],
                                       ["copy.4", 6.0, 2.0, 2.0, 0]],
                               "xfer": []}}, "host": []}
    assert not sc.scoped(trace)
    sc.attach(trace, names)
    assert sc.scoped(trace)
    assert trace["devices"]["0"]["scope"] == {
        "fusion.3": "jit(f)/mlp/exp", "add.2": "jit(f)/loss/add"}
    split = sc.scope_split(trace, 0.0, 10.0)
    assert (split["mlp"], split["loss"], split[sc.UNSCOPED]) == \
        pytest.approx((5e-9, 1e-9, 2e-9))


def test_op_names_of_a_compiled_step_carry_its_scopes():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("loss"):
            return jnp.sum(y * y)

    hlo = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    paths = [sc.scopes_of(p) for p in sc.hlo_op_names(hlo).values()]
    assert any("mlp" in p for p in paths)
    assert any("loss" in p for p in paths)


@pytest.mark.parametrize("metric", ["step.attention_core_share",
                                    "step.recompute_share"])
def test_scope_share_readers(scoped, metric):
    """A share on the scoped slice; silent on a trace with no scope map
    (the program or the runner names nothing)."""
    read = spec.metric_reader(metric)
    t, lo, hi = scoped
    got = read(Measurement({"kind": "train"}, Traced(t, lo, hi), {}))
    assert 0 < got < 100
    plain = _load("train_host_slice.json")
    assert read(Measurement({"kind": "train"}, Traced(*plain), {})) is None
    assert read(Measurement({"kind": "serve"}, Traced(t, lo, hi), {})) \
        is None


def test_wire_bytes_reader():
    read = spec.metric_reader("tier.wire_bytes_per_token")
    traced = Traced({"devices": {}, "host": []}, 0.0, 1.0)
    got = read(Measurement({"kind": "train",
                            "tier_wire_bytes": 3 * 2_264_924_160,
                            "tokens": 3 * 32_768}, traced, {}))
    assert got == 69120
    assert read(Measurement({"kind": "train", "steps": 3}, traced, {})) \
        is None


def test_recompute_share_leaves_out_the_backward_of_the_recompute():
    """The re-run forward counts; the backward that JAX derives from it
    (the attention's backward kernels among it, named
    ``transpose(tier.recompute)``) does not, though it counts as
    attention."""
    body = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
    fwd = body + "tier.recompute/jvp(attention_core)/jit(flash_fwd)/pallas"
    bwd = body + "transpose(tier.recompute)/jvp(attention_core)/" \
        "jit(flash_bwd)/pallas"
    assert "tier.recompute" in sc.forward_scopes_of(fwd)
    assert sc.forward_scopes_of(bwd) == [
        "train_step", "while", "body", "closed_call", "attention_core",
        "flash_bwd", "pallas"]
    trace = {"devices": {"0": {"ops": [["fwd.1", 0.0, 2e8, 2e8, 0],
                                       ["bwd.2", 2e8, 3e8, 3e8, 0],
                                       ["copy.3", 5e8, 1e8, 1e8, 0]],
                               "xfer": []}}, "host": []}
    sc.attach(trace, {"fwd.1": fwd, "bwd.2": bwd})
    traced = Traced(trace, 0.0, 1e9)
    m = Measurement({"kind": "train"}, traced, {})
    assert spec.metric_reader("step.recompute_share")(m) == \
        pytest.approx(100 * 0.2 / m.window_s)
    assert spec.metric_reader("step.attention_core_share")(m) == \
        pytest.approx(100 * 0.5 / m.window_s)
