"""MemoryTier / MemoryRuntime API: registry round-trips, tier composition,
traffic accounting, and gradient equivalence of wrapped vs plain layers on
the CPU backend."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MemoryPlan, MeshPlan
from repro.core.pool import PoolAccountant
from repro.core.runtime import MemoryRuntime
from repro.core.tiers import (CompressedTier, DeviceTier, HostTier,
                              PooledHbmTier, TransferHints, build_tier,
                              get_codec, registered_policies)
from repro.parallel.sharding import ShardingPlanner

SINGLE = MeshPlan((16, 16), ("data", "model"))
PLANNER = ShardingPlanner(SINGLE)


def _plans():
    """Every shipped MemoryPlan config combination the registry must serve."""
    plans = []
    for policy in ("none", "host", "mcdla", "auto", "spill"):
        for placement in ("bw_aware", "local"):
            for compress in ("none", "fp8", "int8"):
                plans.append(MemoryPlan(policy=policy, placement=placement,
                                        compress=compress))
    return plans


# ---------------------------------------------------------------------------
# registry round-trip
def test_registry_covers_all_shipped_policies():
    assert set(registered_policies()) == {"none", "host", "mcdla", "auto",
                                          "spill", "pipeline", "checkpoint"}


@pytest.mark.parametrize("memory", _plans(),
                         ids=lambda m: f"{m.policy}-{m.placement}-{m.compress}")
def test_tier_registry_roundtrip(memory):
    """Every shipped MemoryPlan resolves to a tier whose contract answers
    bandwidth and capacity, and whose stash/fetch round-trips a tensor."""
    memory.validate()
    tier = build_tier(memory, PLANNER)
    bw = tier.bandwidth(SINGLE)
    assert bw > 0
    acct = PoolAccountant(SINGLE, memory)
    assert tier.capacity(acct) > 0

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16), jnp.float32)
    hints = TransferHints(dtype=x.dtype)
    y = tier.fetch(tier.stash(x, hints), hints)
    tol = 0.1 if (memory.compress in ("fp8", "int8")
                  and tier.offloads) else 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=tol,
                               rtol=tol)


def test_unknown_policy_raises():
    with pytest.raises(KeyError):
        build_tier(dataclasses.replace(MemoryPlan(), policy="zram"), PLANNER)


def test_device_tier_does_not_offload():
    tier = build_tier(MemoryPlan(policy="none"), PLANNER)
    assert isinstance(tier, DeviceTier)
    assert not tier.offloads
    # compress on a non-offloading tier is a no-op stack
    tier_c = build_tier(MemoryPlan(policy="none", compress="fp8"), PLANNER)
    assert isinstance(tier_c, DeviceTier)


def test_stash_all_trait():
    assert build_tier(MemoryPlan(policy="mcdla"), PLANNER).stash_all
    assert build_tier(MemoryPlan(policy="host"), PLANNER).stash_all
    assert not build_tier(MemoryPlan(policy="auto"), PLANNER).stash_all


# ---------------------------------------------------------------------------
# composition: CompressedTier over HostTier
def test_compressed_host_composition():
    memory = MemoryPlan(policy="host", compress="fp8")
    tier = build_tier(memory, PLANNER)
    assert isinstance(tier, CompressedTier)
    assert isinstance(tier.inner, HostTier)
    assert tier.describe() == "host+fp8"
    assert tier.payload_ratio() == pytest.approx(0.5)
    # bandwidth contract comes from the host path, not the pool
    pooled = build_tier(MemoryPlan(policy="mcdla"), PLANNER)
    assert tier.bandwidth(SINGLE) < pooled.bandwidth(SINGLE)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
    hints = TransferHints(dtype=jnp.float32)
    payload = tier.stash(x, hints)
    assert payload[1] is not None          # codec scale attached
    y = tier.fetch(payload, hints)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.06
    # allow_compress=False bypasses the codec (bit-exact round-trip)
    raw = tier.stash(x, TransferHints(dtype=jnp.float32,
                                      allow_compress=False))
    assert raw[1] is None
    np.testing.assert_array_equal(
        np.asarray(tier.fetch(raw, hints)), np.asarray(x))


def test_compressed_accounting_halves_pool_bytes():
    memory = MemoryPlan(policy="mcdla", compress="fp8")
    tier = build_tier(memory, PLANNER)
    acct = PoolAccountant(SINGLE, memory)
    tier.account(acct, 1e9)
    plain = build_tier(MemoryPlan(policy="mcdla"), PLANNER)
    acct2 = PoolAccountant(SINGLE, MemoryPlan(policy="mcdla"))
    plain.account(acct2, 1e9)
    assert acct.pooled_bytes == pytest.approx(0.5 * acct2.pooled_bytes)


def test_host_accounting_spares_hbm():
    memory = MemoryPlan(policy="host")
    tier = build_tier(memory, PLANNER)
    acct = PoolAccountant(SINGLE, memory)
    tier.account(acct, 1e9)
    assert acct.pooled_bytes == 0.0
    assert acct.local_bytes == 0.0
    # per-device share of the global stash, like the other acct fields
    assert acct.host_bytes == pytest.approx(1e9 / 256)


def test_device_accounting_is_per_device():
    memory = MemoryPlan(policy="none")
    tier = build_tier(memory, PLANNER)
    acct = PoolAccountant(SINGLE, memory)
    tier.account(acct, 1e9)           # global bytes, batch-sharded
    assert acct.local_bytes == pytest.approx(1e9 / 256)


def test_wire_ratio_skips_uncompressible():
    tier = build_tier(MemoryPlan(policy="mcdla", compress="fp8"), PLANNER)
    xf = jnp.ones((4, 4), jnp.float32)
    xi = jnp.ones((4, 4), jnp.int32)
    assert tier.wire_ratio(xf, TransferHints()) == pytest.approx(0.5)
    assert tier.wire_ratio(xf, TransferHints(allow_compress=False)) == 1.0
    assert tier.wire_ratio(xi, TransferHints()) == 1.0


def test_codec_registry():
    fp8 = get_codec("fp8")
    assert fp8.ratio == pytest.approx(0.5)
    with pytest.raises(KeyError):
        get_codec("zstd")


def test_int8_codec_roundtrip():
    int8 = get_codec("int8")
    assert int8.ratio == pytest.approx(0.5)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 32), jnp.float32)
    q, scale = int8.compress(x)
    assert q.dtype == jnp.int8
    y = int8.decompress(q, scale, jnp.float32)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.02                  # per-tensor int8: <2% relative error


def test_compressed_int8_tier_composition():
    tier = build_tier(MemoryPlan(policy="mcdla", compress="int8"), PLANNER)
    assert isinstance(tier, CompressedTier)
    assert tier.describe() == "pooled_hbm[bw_aware]+int8"
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 16), jnp.float32)
    hints = TransferHints(dtype=jnp.float32)
    y = tier.fetch(tier.stash(x, hints), hints)
    rel = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
    assert rel < 0.02


# ---------------------------------------------------------------------------
# SpillTier: primary until the capacity contract is spent, then overflow
def test_spill_tier_routes_primary_then_overflow():
    from repro.core.tiers import SpillTier
    memory = MemoryPlan(policy="spill")
    primary = PooledHbmTier(PLANNER, None, memory)
    overflow = HostTier(PLANNER, None, memory)
    x = jnp.ones((4, 8), jnp.float32)              # 128 bytes
    tier = SpillTier(primary, overflow, primary_budget=300.0)
    hints = TransferHints(dtype=jnp.float32)

    p1 = tier.stash(x, hints)
    p2 = tier.stash(x, hints)
    p3 = tier.stash(x, hints)                      # 384 > 300: overflows
    assert tier.leg_for(p1) == "primary"
    assert tier.leg_for(p2) == "primary"
    assert tier.leg_for(p3) == "overflow"
    for p in (p1, p2, p3):
        np.testing.assert_array_equal(np.asarray(tier.fetch(p, hints)),
                                      np.asarray(x))
    # discard returns primary budget: the next stash goes primary again
    tier.discard(p1)
    assert tier.leg_for(tier.stash(x, hints)) == "primary"


def test_spill_tier_prices_both_legs():
    from repro.core.tiers import SpillTier
    memory = MemoryPlan(policy="spill")
    tier = build_tier(memory, PLANNER)
    assert isinstance(tier, SpillTier)
    assert tier.describe() == "spill[pooled_hbm[bw_aware]->host]"
    acct = PoolAccountant(SINGLE, memory)
    # capacity: both legs (pool + host DRAM)
    pooled = build_tier(MemoryPlan(policy="mcdla"), PLANNER)
    host = build_tier(MemoryPlan(policy="host"), PLANNER)
    assert tier.capacity(acct) == pytest.approx(
        pooled.capacity(acct) + host.capacity(acct))
    # bandwidth: the primary leg while it has headroom, degraded toward
    # the host leg once the budget is spent
    assert tier.bandwidth(SINGLE) == pytest.approx(pooled.bandwidth(SINGLE))
    small = SpillTier(PooledHbmTier(PLANNER, None, memory),
                      HostTier(PLANNER, None, memory), primary_budget=64.0)
    small.stash(jnp.ones((16, 16), jnp.float32),
                TransferHints(dtype=jnp.float32))  # overflows immediately
    small.stash(jnp.ones((16, 16), jnp.float32),
                TransferHints(dtype=jnp.float32))
    assert small.bandwidth(SINGLE) < pooled.bandwidth(SINGLE)
    assert small.bandwidth(SINGLE) > 0.0


def test_spill_payload_survives_pytree():
    """The leg routing is static treedef data: jit residuals keep it."""
    from repro.core.tiers import SpillPayload
    p = SpillPayload("overflow", 128.0, (jnp.ones((2, 2)), None))
    leaves, treedef = jax.tree_util.tree_flatten(p)
    q = jax.tree_util.tree_unflatten(treedef, leaves)
    assert q.leg == "overflow" and q.nbytes == 128.0
    np.testing.assert_array_equal(np.asarray(q.inner[0]), np.ones((2, 2)))


# ---------------------------------------------------------------------------
# bandwidth contract ordering (paper Fig. 10 / §IV)
def test_bandwidth_contract_orders():
    bw_aware = build_tier(MemoryPlan(policy="mcdla", placement="bw_aware"),
                          PLANNER)
    local = build_tier(MemoryPlan(policy="mcdla", placement="local"), PLANNER)
    host = build_tier(MemoryPlan(policy="host"), PLANNER)
    assert bw_aware.bandwidth(SINGLE) >= local.bandwidth(SINGLE)
    assert local.bandwidth(SINGLE) > host.bandwidth(SINGLE)


def test_pooled_capacity_exceeds_device():
    memory = MemoryPlan(policy="mcdla")
    pooled = build_tier(memory, PLANNER)
    device = build_tier(MemoryPlan(policy="none"), PLANNER)
    acct = PoolAccountant(SINGLE, memory)
    assert pooled.capacity(acct) == pytest.approx(acct.budget * 256)
    assert device.capacity(acct) == pytest.approx(acct.budget)


# ---------------------------------------------------------------------------
# gradient equivalence of wrapped vs plain layers, per tier, CPU backend
def _layer(params, x, pos):
    h = jnp.einsum("bsd,df->bsf", x, params["w1"])
    h = jax.nn.silu(h) + pos.astype(h.dtype)[None, :, None] * 0.0
    return x + jnp.einsum("bsf,fd->bsd", h, params["w2"])


def _setup():
    key = jax.random.PRNGKey(0)
    B, S, D, F = 4, 8, 16, 32
    params = {"w1": jax.random.normal(key, (D, F)) * 0.1,
              "w2": jax.random.normal(jax.random.PRNGKey(2), (F, D)) * 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D))
    pos = jnp.arange(S, dtype=jnp.int32)
    return params, x, pos


@pytest.mark.parametrize("memory", [
    MemoryPlan(policy="none"),
    MemoryPlan(policy="mcdla"),
    MemoryPlan(policy="mcdla", placement="local"),
    MemoryPlan(policy="auto"),
    MemoryPlan(policy="host"),
], ids=lambda m: f"{m.policy}-{m.placement}")
def test_wrapped_gradients_match_plain(memory):
    params, x, pos = _setup()
    runtime = MemoryRuntime(SINGLE, memory)
    wrapped = runtime.wrap_layer(_layer, compute_spec=None)

    def loss(fn, p, xx):
        return jnp.sum(fn(p, xx, pos) ** 2)

    v = loss(wrapped, params, x)
    vref = loss(_layer, params, x)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vref), rtol=1e-5)
    g = jax.grad(lambda p, xx: loss(wrapped, p, xx), argnums=(0, 1))(params, x)
    gref = jax.grad(lambda p, xx: loss(_layer, p, xx), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_wrapped_gradients_fp8_close():
    params, x, pos = _setup()
    runtime = MemoryRuntime(SINGLE, MemoryPlan(policy="mcdla", compress="fp8"))
    wrapped = runtime.wrap_layer(_layer, compute_spec=None)
    g = jax.grad(lambda p, xx: jnp.sum(wrapped(p, xx, pos) ** 2),
                 argnums=(0, 1))(params, x)
    gref = jax.grad(lambda p, xx: jnp.sum(_layer(p, xx, pos) ** 2),
                    argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref)):
        a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
        cos = (a @ b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12)
        assert cos > 0.99


def test_aux_fetch_derives_own_layout():
    """Aux tensors whose rank differs from the residual must not inherit a
    static residual compute_spec (the old code crashed / mis-constrained)."""
    from jax.sharding import PartitionSpec as P

    params, x, pos = _setup()
    enc = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 2, 8))  # rank 4

    def layer_with_aux(p, xx, enc_states):
        mixed = xx + jnp.mean(enc_states, axis=2) @ jnp.eye(
            enc_states.shape[-1], xx.shape[-1], dtype=xx.dtype)
        return _layer(p, mixed, jnp.arange(xx.shape[1], dtype=jnp.int32))

    runtime = MemoryRuntime(SINGLE, MemoryPlan(policy="mcdla"))
    # static rank-3 residual spec; aux is rank 4 — must derive its own
    wrapped = runtime.wrap_layer(layer_with_aux,
                                 compute_spec=P("data", None, None))
    g = jax.grad(lambda p, xx, e: jnp.sum(wrapped(p, xx, e) ** 2),
                 argnums=(0, 1, 2))(params, x, enc)
    gref = jax.grad(lambda p, xx, e: jnp.sum(layer_with_aux(p, xx, e) ** 2),
                    argnums=(0, 1, 2))(params, x, enc)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# runtime facade
def test_runtime_traffic_report():
    params, x, pos = _setup()
    runtime = MemoryRuntime(SINGLE, MemoryPlan(policy="mcdla"))
    wrapped = runtime.wrap_layer(_layer, compute_spec=None)
    jax.grad(lambda p, xx: jnp.sum(wrapped(p, xx, pos) ** 2))(params, x)
    rep = runtime.traffic_report()
    raw = float(x.size) * x.dtype.itemsize
    assert rep["tier"] == "pooled_hbm[bw_aware]"
    # eager: counted as it ran, and no step was recorded
    assert rep["stash"]["raw_bytes"] == pytest.approx(raw)
    assert rep["fetch"]["raw_bytes"] == pytest.approx(raw)
    assert rep["stash"]["per_step"] == 0.0
    runtime.reset_traffic()
    assert runtime.traffic_report()["wire_bytes_total"] == 0.0
    # recorded as one step's traffic: counted only when the step runs
    with runtime.recording_step():
        jax.grad(lambda p, xx: jnp.sum(wrapped(p, xx, pos) ** 2))(params, x)
    rep = runtime.traffic_report()
    assert rep["stash"]["per_step"] == rep["fetch"]["per_step"] == \
        pytest.approx(raw)
    assert rep["wire_bytes_total"] == 0.0
    runtime.count_step()
    assert runtime.traffic_report()["wire_bytes_total"] == \
        pytest.approx(2 * raw)
    assert "tier=" in runtime.traffic_summary()


def test_runtime_no_offload_is_identity():
    runtime = MemoryRuntime(SINGLE, MemoryPlan(policy="none"))
    assert runtime.wrap_layer(_layer) is _layer
    assert runtime.resolve_stash_groups(None, None, 12) == 0


def test_runtime_resolves_stash_groups():
    from repro.configs import SHAPES_BY_NAME, get_arch

    cfg = get_arch("smollm-135m")
    shape = SHAPES_BY_NAME["train_4k"]
    mc = MemoryRuntime(SINGLE, MemoryPlan(policy="mcdla"))
    assert mc.resolve_stash_groups(cfg, shape, cfg.num_layers) == \
        cfg.num_layers
    auto = MemoryRuntime(SINGLE, MemoryPlan(policy="auto"))
    k = auto.resolve_stash_groups(cfg, shape, cfg.num_layers)
    assert 0 <= k <= cfg.num_layers


# ---------------------------------------------------------------------------
# one device: a host tier still stashes, a pool of one says it cannot
def _one_device_train_step(policy: str):
    from repro.configs import RunConfig, TrainConfig, get_arch
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model
    from repro.train.loop import make_train_step
    from repro.train.train_state import init_state

    cfg = get_arch("smollm-135m").reduced()
    B, S = 2, 16
    tc = TrainConfig(total_steps=1, warmup_steps=0)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, "train"),
                    mesh=MeshPlan((1,), ("data",)),
                    memory=MemoryPlan(policy=policy), train=tc)
    model = build_model(run)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens,
             "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
    _, metrics = jax.jit(make_train_step(model, tc))(init_state(model, tc),
                                                     batch)
    assert np.isfinite(float(metrics["loss"]))
    return model.runtime


def test_host_policy_stashes_on_one_device():
    """No mesh, --policy host: every layer's input still leaves through
    the host tier (metered stash and fetch bytes above 0)."""
    rep = _one_device_train_step("host").traffic_report()
    assert rep["stash"]["wire_bytes"] > 0
    assert rep["fetch"]["wire_bytes"] == rep["stash"]["wire_bytes"]


def test_pool_of_one_is_degenerate_and_says_so(caplog):
    with caplog.at_level("INFO", logger="repro.core.runtime"):
        runtime = _one_device_train_step("mcdla")
    assert not runtime.moves_bytes
    assert runtime.traffic_report()["wire_bytes_total"] == 0.0
    notes = [r for r in caplog.records if "nowhere to stash" in r.message]
    assert len(notes) == 1


def test_host_memory_placement_is_a_platform_check():
    """XLA:CPU drops memory-space transfers in traced code; the tier says
    so through HostTier.places_host_memory, keyed on the backend."""
    assert HostTier.places_host_memory() == (
        jax.default_backend() in ("tpu", "gpu"))
    tier = HostTier(PLANNER, None, MemoryPlan(policy="host"))
    assert tier.leaves_device()
    x = jnp.arange(8.0)
    payload, _ = tier.stash(x, TransferHints())
    if not HostTier.places_host_memory():
        assert payload is x
    np.testing.assert_array_equal(np.asarray(tier.fetch((payload, None),
                                                        TransferHints())),
                                  np.asarray(x))


def test_cost_model_chip_follows_the_device(monkeypatch):
    """MemoryRuntime prices tiers on the attached TPU's entry in
    hw.CHIPS_BY_KIND; an unknown TPU kind raises; off-TPU the analytic
    target is TPU_V5E."""
    from types import SimpleNamespace

    from repro import hw

    assert MemoryRuntime(SINGLE, MemoryPlan()).chip is hw.TPU_V5E

    def fake(kind):
        monkeypatch.setattr(jax, "devices", lambda: [
            SimpleNamespace(platform="tpu", device_kind=kind)])

    fake("TPU v5 lite")
    assert MemoryRuntime(SINGLE, MemoryPlan()).chip is hw.TPU_V5E
    fake("TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        MemoryRuntime(SINGLE, MemoryPlan())
