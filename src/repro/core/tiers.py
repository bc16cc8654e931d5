"""Pluggable ``MemoryTier`` backing stores — the paper's memory hierarchy as
a first-class API.

The paper's central claim (§III) is *transparent* memory-capacity expansion:
the runtime decides where a tensor lives — device HBM, pooled HBM over the
device-side interconnect, or host DRAM — without the model knowing.  This
module is that decision surface.  Each backing store is a :class:`MemoryTier`
with a uniform contract:

  ``stash(x, hints)``      copy-out to the tier; returns an opaque payload
  ``fetch(payload, hints)`` prefetch back, restored to the compute layout
  ``bandwidth(plan, chip)`` per-device stash/fetch bandwidth (cost model)
  ``capacity(accountant)``  bytes one device can address through the tier
  ``account(acct, nbytes)`` charge a stashed tensor to the boot-time map

Shipped tiers (DESIGN.md §3):

* :class:`DeviceTier`     — KEEP / the oracle DC-DLA(O): nothing leaves HBM.
* :class:`PooledHbmTier`  — MC-DLA: the aggregate HBM of the mesh reached
  over ICI, BW_AWARE or LOCAL placement (core/pool.py, paper Fig. 10).
* :class:`HostTier`       — DC-DLA baseline: pinned host memory over PCIe.
* :class:`CompressedTier` — decorator adding the memory-node's "optional
  compression ASIC" (§III-A) to any tier; codecs are registry-extensible
  (fp8 ships; int8/zstd-style codecs slot in via :func:`register_codec`).
* :class:`SpillTier`      — decorator: primary tier until its capacity
  contract is spent, then overflow to a cheaper store.
* :class:`PipelineStageTier` — decorator: per-stage activation stash for
  pipeline schedules (1F1B), priced as the DCN stage hop in series with
  the backing store (ROADMAP "pipeline-parallel stage tier").
* :class:`CheckpointTier`  — decorator: the durable snapshot leg — the
  ``CheckpointManager`` writes through it, metered as ``ckpt_save`` /
  ``ckpt_load`` and priced as the DCN drain in series with the backing
  store (ROADMAP "checkpoint-as-a-tier").

Policies map to tiers through :func:`build_tier` — the ONLY place in the
codebase that branches on ``MemoryPlan.policy`` strings.  Everything else
(models, train, serve, sim, the planner) dispatches through the tier object
or the :class:`repro.core.runtime.MemoryRuntime` facade.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import hw
from repro.configs.base import MemoryPlan, MeshPlan
from repro.core import compress as comp
from repro.core.pool import PoolAccountant, PoolAxes, pool_spec
from repro.parallel.sharding import ShardingPlanner

# (data, optional codec scale) — the opaque unit a tier hands back from stash
Payload = Tuple[jax.Array, Optional[jax.Array]]


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TransferHints:
    """Per-call context a tier may consult when placing a tensor.

    compute_spec: the layout the *compute* wants the tensor back in — a
      static PartitionSpec, a shape-aware callable ``shape -> spec``, or
      None (leave the tier's layout in place).
    batch_dim: index of the global-batch dim (pool placement stripes it).
    dtype: dtype to restore on fetch (codecs decompress into it).
    allow_compress: False for tensors that must round-trip bit-exactly
      (e.g. aux residuals validated against an uncompressed oracle).
    name: label for sharding-planner fallbacks and traffic accounting.
    """

    compute_spec: object = None
    batch_dim: int = 0
    dtype: Optional[jnp.dtype] = None
    allow_compress: bool = True
    name: str = "stash"

    def resolved_spec(self, shape) -> Optional[P]:
        if self.compute_spec is None:
            return None
        if callable(self.compute_spec):
            return self.compute_spec(shape)
        return self.compute_spec


# ---------------------------------------------------------------------------
class MemoryTier(abc.ABC):
    """One backing store of the memory hierarchy.

    Tiers are built once per run by :func:`build_tier` and threaded through
    :class:`repro.core.runtime.MemoryRuntime`; they hold the (planner, mesh)
    pair so call sites never hand-thread sharding state again.
    """

    #: short id used in reports and the registry
    kind: str = "abstract"

    def __init__(self, planner: ShardingPlanner, mesh: Optional[Mesh],
                 memory: MemoryPlan, *, stash_all: bool = True):
        self.planner = planner
        self.mesh = mesh
        self.memory = memory
        # policy trait: stash every layer (paper's stress-test mode) vs let
        # the KEEP/POOL/RECOMPUTE planner choose a stash fraction.
        self.stash_all = stash_all

    # -- data path ---------------------------------------------------------
    @abc.abstractmethod
    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        """Copy-out ``x`` to the tier; returns an opaque payload."""

    @abc.abstractmethod
    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        """Prefetch a payload back into the compute layout."""

    # -- cost contract -----------------------------------------------------
    @abc.abstractmethod
    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        """Per-device stash/fetch bandwidth in bytes/s (cost-model input)."""

    @abc.abstractmethod
    def capacity(self, accountant: PoolAccountant) -> float:
        """Bytes one device can address through this tier (paper Fig. 10
        boot-time memory map)."""

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        """Charge a stashed tensor of global ``nbytes`` to the memory map."""
        accountant.alloc_pooled(nbytes)

    # -- traits ------------------------------------------------------------
    @property
    def offloads(self) -> bool:
        """False when stashing is a no-op (tensors stay resident)."""
        return True

    def leaves_device(self) -> bool:
        """Whether a stash moves bytes out of this device's HBM on this
        tier's mesh (False for a tier that is degenerate here, such as a
        pool of one device)."""
        return self.offloads

    def payload_ratio(self) -> float:
        """Stashed bytes per raw byte (codecs shrink this below 1)."""
        return 1.0

    def wire_ratio(self, x: jax.Array, hints: TransferHints) -> float:
        """Actual bytes-per-raw-byte for THIS transfer — unlike
        ``payload_ratio`` it accounts for tensors a codec would skip
        (non-float dtypes, ``allow_compress=False``)."""
        return 1.0

    def describe(self) -> str:
        return self.kind

    # -- helpers -----------------------------------------------------------
    def _constrain(self, x: jax.Array, spec: Optional[P]) -> jax.Array:
        if spec is None or self.mesh is None or self.mesh.size <= 1:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
class DeviceTier(MemoryTier):
    """KEEP / oracle tier: the tensor never leaves device HBM.

    ``stash`` is the identity — this is DC-DLA(O), the paper's
    infinite-memory baseline, and the KEEP arm of the auto planner.
    """

    kind = "device"

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        return (x, None)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        return payload[0]

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        return chip.hbm_bw

    def capacity(self, accountant: PoolAccountant) -> float:
        return accountant.budget

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        # global bytes stay resident, batch-sharded across the devices
        accountant.alloc_local(nbytes / max(accountant.plan.num_devices, 1))

    @property
    def offloads(self) -> bool:
        return False


# ---------------------------------------------------------------------------
class PooledHbmTier(MemoryTier):
    """MC-DLA: the aggregate HBM of the mesh as the backing store.

    A stashed tensor is re-sharded so every chip of the pool keeps only
    1/pool_size of it (core/pool.py BW_AWARE/LOCAL placements, paper
    Fig. 10) and all-gathered over ICI right before its backward use.
    """

    kind = "pooled_hbm"

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        spec = pool_spec(x.shape, self.planner, self.memory.placement,
                         hints.batch_dim, name=hints.name)
        return (self._constrain(x, spec), None)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        x, _ = payload
        return self._constrain(x, hints.resolved_spec(x.shape))

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        """bw_aware engages the ICI links of every mesh dimension the pool
        spans (paper Fig. 10: all N links, left+right nodes); local engages
        one dimension's links.  A 2D torus gives 2 links per dimension."""
        dims = len(PoolAxes(plan).axes_for(self.memory.placement))
        links = min(2 * dims, chip.num_links)
        return links * chip.link_bw

    def capacity(self, accountant: PoolAccountant) -> float:
        return accountant.system_capacity()

    def pool_devices(self, plan: MeshPlan) -> int:
        return PoolAxes(plan).pool_size(self.memory.placement)

    def leaves_device(self) -> bool:
        return self.mesh is not None and self.mesh.size > 1

    def describe(self) -> str:
        return f"{self.kind}[{self.memory.placement}]"


# ---------------------------------------------------------------------------
class HostTier(MemoryTier):
    """DC-DLA baseline: virtualize against pinned host memory over PCIe.

    A stash moves the array to the ``pinned_host`` memory space
    (``jax.memory.Space.Host``), a fetch moves it back to ``device``;
    the array keeps its sharding either way.
    """

    kind = "host"

    @staticmethod
    def places_host_memory() -> bool:
        """Whether the backend keeps host-space arrays off the device.

        TPU and GPU lower a transfer to ``pinned_host`` to a real copy out
        of HBM, inside jitted programs too.  XLA:CPU has no memory but the
        host's: its lowering drops memory-space transfers in traced code,
        so there the tier meters its traffic and the bytes stay where they
        are (the DC/HC/MC contrast is reproduced in ``sim/``)."""
        return jax.default_backend() in ("tpu", "gpu")

    @classmethod
    def _transfer(cls, x: jax.Array, space) -> jax.Array:
        if not cls.places_host_memory():
            return x
        return jax.device_put(x, space)

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        return (self._transfer(x, jax.memory.Space.Host), None)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        return self._transfer(payload[0], jax.memory.Space.Device)

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        """PCIe path, root-complex shared across the host's devices (paper
        §I: per-device host bandwidth divides by intra-node device count)."""
        local_devices = max(1, min(plan.num_devices, hw.DEVICES_PER_HOST))
        shared = 2 * hw.PCIE_ROOT_PER_SOCKET / local_devices
        return min(hw.PCIE_GEN3_BW, shared)

    def capacity(self, accountant: PoolAccountant) -> float:
        return hw.HOST_DRAM_BYTES

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        # each device parks its own shard in host DRAM (per-device share,
        # matching the accountant's other per-device fields)
        accountant.alloc_host(nbytes / max(accountant.plan.num_devices, 1))


# ---------------------------------------------------------------------------
# codec registry — the memory-node's "optional compression ASIC" (§III-A).
# The registry itself lives in core/compress.py (codecs carry Pallas kernel
# twins there); these aliases keep the historical import path working.
Codec = comp.Codec
register_codec = comp.register_codec
get_codec = comp.get_codec
registered_codecs = comp.registered_codecs


class CompressedTier(MemoryTier):
    """Decorator: quantize-and-pack before any tier's stash collective.

    Halves (fp8) the bytes that cross the wire AND that occupy the inner
    tier — composable with pooled HBM and host alike, subsuming the old
    ``allow_compress`` flag threading.
    """

    kind = "compressed"

    def __init__(self, inner: MemoryTier, codec: str = "fp8"):
        super().__init__(inner.planner, inner.mesh, inner.memory,
                         stash_all=inner.stash_all)
        self.inner = inner
        self.codec = get_codec(codec)

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        if not hints.allow_compress or not self.codec.applies_to(x):
            return self.inner.stash(x, hints)
        q, scale = self.codec.compress(x)
        payload, _ = self.inner.stash(q, hints)
        return (payload, scale)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        q, scale = payload
        if scale is None:
            return self.inner.fetch(payload, hints)
        # fetch the packed bytes through the inner tier in its own layout,
        # decompress, then restore the compute layout
        raw = self.inner.fetch(
            (q, None), dataclasses.replace(hints, compute_spec=None))
        x = self.codec.decompress(raw, scale, hints.dtype or jnp.bfloat16)
        return self._constrain(x, hints.resolved_spec(x.shape))

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        return self.inner.bandwidth(plan, chip)

    def capacity(self, accountant: PoolAccountant) -> float:
        return self.inner.capacity(accountant)

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        self.inner.account(accountant, nbytes * self.codec.ratio)

    @property
    def offloads(self) -> bool:
        return self.inner.offloads

    def leaves_device(self) -> bool:
        return self.inner.leaves_device()

    def payload_ratio(self) -> float:
        return self.codec.ratio * self.inner.payload_ratio()

    def wire_ratio(self, x: jax.Array, hints: TransferHints) -> float:
        if hints.allow_compress and self.codec.applies_to(x):
            return self.codec.ratio * self.inner.wire_ratio(x, hints)
        return self.inner.wire_ratio(x, hints)

    def describe(self) -> str:
        return f"{self.inner.describe()}+{self.codec.name}"


# ---------------------------------------------------------------------------
class SpillPayload:
    """Payload of a :class:`SpillTier` stash: the inner leg's payload plus a
    *static* record of which leg took it and how many bytes it charged.

    Registered as a pytree node with (leg, nbytes) in the treedef so the
    routing decision — made at trace time by the Python-side capacity
    counter — survives jit residuals without becoming a traced value.
    """

    __slots__ = ("leg", "nbytes", "inner")

    def __init__(self, leg: str, nbytes: float, inner: Payload):
        self.leg = leg              # "primary" | "overflow"
        self.nbytes = nbytes        # bytes charged against the primary budget
        self.inner = inner

    def __repr__(self) -> str:
        return f"SpillPayload(leg={self.leg!r}, nbytes={self.nbytes:.0f})"


jax.tree_util.register_pytree_node(
    SpillPayload,
    lambda p: ((p.inner,), (p.leg, p.nbytes)),
    lambda aux, children: SpillPayload(aux[0], aux[1], children[0]))


class SpillTier(MemoryTier):
    """Decorator: primary tier until its capacity contract is exhausted,
    then overflow to a cheaper backing store.

    The ROADMAP's host+pool composition (Buddy-Compression-style cold-page
    demotion, arXiv:1903.02596): stash to the *primary* leg (e.g. pooled
    HBM) while the boot-time capacity contract has headroom, and overflow
    to the *overflow* leg (e.g. host DRAM) once it is spent.  The routing
    decision is taken per-stash at trace time against a Python-side byte
    counter, so the same object works inside jit (static routing) and in
    the serving host loop (dynamic slot churn via :meth:`discard`).

    The planner prices both legs: each leg is itself a full
    :class:`MemoryTier`, and the blended :meth:`bandwidth` degrades from
    the primary's toward the occupancy-weighted harmonic mean as the
    primary fills.
    """

    kind = "spill"

    def __init__(self, primary: MemoryTier, overflow: MemoryTier,
                 primary_budget: Optional[float] = None):
        super().__init__(primary.planner, primary.mesh, primary.memory,
                         stash_all=primary.stash_all)
        self.primary = primary
        self.overflow = overflow
        if primary_budget is None:
            acct = PoolAccountant(primary.planner.plan, primary.memory)
            primary_budget = primary.capacity(acct)
        self.primary_budget = float(primary_budget)
        self._primary_used = 0.0
        self._overflow_used = 0.0

    # -- routing -----------------------------------------------------------
    def _charge_bytes(self, x: jax.Array) -> float:
        raw = float(x.size) * jnp.dtype(x.dtype).itemsize
        return raw * self.primary.payload_ratio()

    def primary_headroom(self) -> float:
        return self.primary_budget - self._primary_used

    def reset(self) -> None:
        self._primary_used = 0.0
        self._overflow_used = 0.0

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        nbytes = self._charge_bytes(x)
        if nbytes <= self.primary_headroom():
            self._primary_used += nbytes
            return (SpillPayload("primary", nbytes,
                                 self.primary.stash(x, hints)), None)
        self._overflow_used += nbytes
        return (SpillPayload("overflow", nbytes,
                             self.overflow.stash(x, hints)), None)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        sp = payload[0]
        leg = self.primary if sp.leg == "primary" else self.overflow
        return leg.fetch(sp.inner, hints)

    def discard(self, payload: Payload) -> None:
        """Release a stashed slot's budget charge (serving slot churn)."""
        sp = payload[0]
        if sp.leg == "primary":
            self._primary_used = max(0.0, self._primary_used - sp.nbytes)
        else:
            self._overflow_used = max(0.0, self._overflow_used - sp.nbytes)

    def leg_for(self, payload: Payload) -> str:
        return payload[0].leg

    # -- cost contract: both legs priced -----------------------------------
    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        """Occupancy-blended: all-primary while nothing has overflowed,
        then the harmonic mean weighted by the routed byte fractions
        (bytes on each leg stream at that leg's rate)."""
        bw_p = self.primary.bandwidth(plan, chip)
        if self._overflow_used <= 0.0:
            return bw_p
        bw_o = self.overflow.bandwidth(plan, chip)
        total = self._primary_used + self._overflow_used
        f_over = self._overflow_used / total
        return 1.0 / ((1.0 - f_over) / bw_p + f_over / bw_o)

    def capacity(self, accountant: PoolAccountant) -> float:
        return self.primary_budget + self.overflow.capacity(accountant)

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        if nbytes <= self.primary_headroom():
            self.primary.account(accountant, nbytes)
        else:
            self.overflow.account(accountant, nbytes)

    def leaves_device(self) -> bool:
        return self.primary.leaves_device() or self.overflow.leaves_device()

    def payload_ratio(self) -> float:
        return self.primary.payload_ratio()

    def wire_ratio(self, x: jax.Array, hints: TransferHints) -> float:
        if self._charge_bytes(x) <= self.primary_headroom():
            return self.primary.wire_ratio(x, hints)
        return self.overflow.wire_ratio(x, hints)

    def describe(self) -> str:
        return (f"{self.kind}[{self.primary.describe()}"
                f"->{self.overflow.describe()}]")


# ---------------------------------------------------------------------------
class PipelineStageTier(MemoryTier):
    """Decorator: per-stage activation backing store for pipeline schedules.

    The training half of the tier unification (ROADMAP "pipeline-parallel
    stage tier"): a 1F1B schedule's saved stage inputs leave the stage's
    HBM for a backing store instead of staying implicitly live, so the
    KEEP/POOL/RECOMPUTE planner can trade pipeline bubbles against pool
    traffic with the same cost contract it prices every other tier with.

    * ``bandwidth`` — the DCN stage hop in *series* with the backing
      store: bytes cross the inter-stage link and then the inner tier's
      stash collective, so the harmonic composition bounds both.
    * ``capacity`` — each stage addresses its 1/n_stages share of the
      backing store (stages stash concurrently into the same pool).
    * data path — delegates to the inner tier; composes with
      :class:`CompressedTier` / :class:`SpillTier` like any other
      decorator (``build_stage_tier`` stacks the configured codec).
    """

    kind = "pipeline_stage"

    def __init__(self, inner: MemoryTier, n_stages: int = 1):
        super().__init__(inner.planner, inner.mesh, inner.memory,
                         stash_all=inner.stash_all)
        self.inner = inner
        self.n_stages = max(1, n_stages)

    def set_stages(self, n_stages: int) -> None:
        self.n_stages = max(1, n_stages)

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        return self.inner.stash(x, hints)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        return self.inner.fetch(payload, hints)

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        inner_bw = self.inner.bandwidth(plan, chip)
        if inner_bw <= 0:
            return hw.DCN_BW
        return 1.0 / (1.0 / hw.DCN_BW + 1.0 / inner_bw)

    def capacity(self, accountant: PoolAccountant) -> float:
        return self.inner.capacity(accountant) / self.n_stages

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        self.inner.account(accountant, nbytes)

    @property
    def offloads(self) -> bool:
        return self.inner.offloads

    def leaves_device(self) -> bool:
        return self.inner.leaves_device()

    def payload_ratio(self) -> float:
        return self.inner.payload_ratio()

    def wire_ratio(self, x: jax.Array, hints: TransferHints) -> float:
        return self.inner.wire_ratio(x, hints)

    def describe(self) -> str:
        return f"{self.kind}[{self.n_stages}x{self.inner.describe()}]"


class CheckpointTier(MemoryTier):
    """Decorator: the durable snapshot leg of the memory hierarchy.

    A checkpoint is the coldest tensor class of all — touched once per
    cadence, read only on failure — so it belongs in the pool, not in a
    side-channel that bypasses the tier API (ISSUE 6 / ROADMAP
    "checkpoint-as-a-tier").  The decorator delegates the data path to its
    backing store (host DRAM or pooled HBM, with an optional codec stacked
    on top by :func:`build_ckpt_tier`) and prices durability:

    * ``bandwidth`` — a snapshot is only fault-tolerant once it leaves the
      failure domain, so the drain is the DCN hop in *series* with the
      backing store's stash collective (same harmonic composition as
      :class:`PipelineStageTier`'s stage hop).
    * ``capacity`` — ``keep`` live snapshots must fit concurrently: each
      addresses 1/keep of the backing store.
    """

    kind = "ckpt"

    def __init__(self, inner: MemoryTier, keep: int = 1):
        super().__init__(inner.planner, inner.mesh, inner.memory,
                         stash_all=inner.stash_all)
        self.inner = inner
        self.keep = max(1, keep)

    def stash(self, x: jax.Array, hints: TransferHints) -> Payload:
        return self.inner.stash(x, hints)

    def fetch(self, payload: Payload, hints: TransferHints) -> jax.Array:
        return self.inner.fetch(payload, hints)

    def bandwidth(self, plan: MeshPlan, chip: hw.Chip = hw.TPU_V5E) -> float:
        inner_bw = self.inner.bandwidth(plan, chip)
        if inner_bw <= 0:
            return hw.DCN_BW
        return 1.0 / (1.0 / hw.DCN_BW + 1.0 / inner_bw)

    def capacity(self, accountant: PoolAccountant) -> float:
        return self.inner.capacity(accountant) / self.keep

    def account(self, accountant: PoolAccountant, nbytes: float) -> None:
        self.inner.account(accountant, nbytes)

    @property
    def offloads(self) -> bool:
        # a checkpoint always leaves the device, even over a DeviceTier
        # backing (the drain hop is the point)
        return True

    def payload_ratio(self) -> float:
        return self.inner.payload_ratio()

    def wire_ratio(self, x: jax.Array, hints: TransferHints) -> float:
        return self.inner.wire_ratio(x, hints)

    def describe(self) -> str:
        return f"{self.kind}[{self.inner.describe()}]"


def build_ckpt_tier(memory: MemoryPlan, planner: ShardingPlanner,
                    mesh: Optional[Mesh] = None,
                    backing: str = "host", codec: str = "none",
                    keep: int = 1) -> MemoryTier:
    """The snapshot tier for a run: the requested backing store behind the
    durability drain, with the snapshot codec stacked on top.  Mirrors
    :func:`build_stage_tier` — the backing policy resolves through the
    registry, so a new store prices checkpoints without touching this."""
    if backing in ("none", "pipeline", "checkpoint"):
        backing = "host"
    binding = _TIER_REGISTRY[backing]
    inner = binding.factory(memory, planner, mesh)
    inner.stash_all = binding.stash_all
    tier: MemoryTier = CheckpointTier(inner, keep=keep)
    if codec != "none":
        tier = CompressedTier(tier, codec)
    return tier


def build_stage_tier(memory: MemoryPlan, planner: ShardingPlanner,
                     mesh: Optional[Mesh] = None,
                     n_stages: int = 1) -> MemoryTier:
    """The stage tier for a pipeline run: the memory plan's own backing
    store (pooled HBM when the policy keeps everything resident) behind the
    per-stage DCN hop, with the configured codec stacked on top."""
    backing = memory.policy if memory.policy not in ("none", "pipeline") \
        else "mcdla"
    binding = _TIER_REGISTRY[backing]
    inner = binding.factory(memory, planner, mesh)
    inner.stash_all = binding.stash_all
    tier: MemoryTier = PipelineStageTier(inner, n_stages=n_stages)
    if memory.compress != "none":
        tier = CompressedTier(tier, memory.compress)
    return tier


# ---------------------------------------------------------------------------
# tier registry: MemoryPlan.policy -> tier.  The one sanctioned policy-string
# dispatch in the codebase (everything else goes through the tier object).
TierFactory = Callable[[MemoryPlan, ShardingPlanner, Optional[Mesh]],
                       MemoryTier]


@dataclasses.dataclass(frozen=True)
class TierBinding:
    factory: TierFactory
    stash_all: bool          # stash every layer vs planner-chosen fraction


_TIER_REGISTRY: Dict[str, TierBinding] = {}


def register_tier(policy: str, factory: TierFactory,
                  stash_all: bool = True) -> None:
    _TIER_REGISTRY[policy] = TierBinding(factory, stash_all)


def registered_policies() -> Tuple[str, ...]:
    return tuple(sorted(_TIER_REGISTRY))


def build_tier(memory: MemoryPlan, planner: ShardingPlanner,
               mesh: Optional[Mesh] = None) -> MemoryTier:
    """Resolve a :class:`MemoryPlan` to its tier stack.

    Configs stay plain serializable dataclasses; this is where the policy
    string becomes an object.  ``compress != 'none'`` wraps the tier in a
    :class:`CompressedTier` (a no-op stack on the device tier, which never
    moves bytes).
    """
    if memory.policy not in _TIER_REGISTRY:
        raise KeyError(f"unknown memory policy {memory.policy!r}; "
                       f"registered: {registered_policies()}")
    binding = _TIER_REGISTRY[memory.policy]
    tier = binding.factory(memory, planner, mesh)
    tier.stash_all = binding.stash_all
    if memory.compress != "none" and tier.offloads:
        tier = CompressedTier(tier, memory.compress)
    return tier


register_tier("none",
              lambda m, p, mesh: DeviceTier(p, mesh, m), stash_all=False)
register_tier("host",
              lambda m, p, mesh: HostTier(p, mesh, m), stash_all=True)
register_tier("mcdla",
              lambda m, p, mesh: PooledHbmTier(p, mesh, m), stash_all=True)
# "auto" uses the same pooled tier; the KEEP/POOL/RECOMPUTE planner
# (core/policy.py) decides the stash fraction instead of stashing all.
register_tier("auto",
              lambda m, p, mesh: PooledHbmTier(p, mesh, m), stash_all=False)
# "spill": pooled HBM until the pool's capacity contract is spent, host
# DRAM past it (ROADMAP host+pool composition).
register_tier("spill",
              lambda m, p, mesh: SpillTier(PooledHbmTier(p, mesh, m),
                                           HostTier(p, mesh, m)),
              stash_all=True)
# "pipeline": the pipeline-stage tier over pooled HBM (stage count is
# late-bound by the run via set_stages; build_stage_tier is the usual way
# to construct it with the right backing store + codec stack).
register_tier("pipeline",
              lambda m, p, mesh: PipelineStageTier(PooledHbmTier(p, mesh, m)),
              stash_all=True)
# "checkpoint": the durable snapshot leg over host DRAM (build_ckpt_tier is
# the usual way to construct it with a pooled backing + codec stack).
register_tier("checkpoint",
              lambda m, p, mesh: CheckpointTier(HostTier(p, mesh, m)),
              stash_all=True)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TierSpec:
    """Hardware-model-level bandwidth/capacity contract of a backing store.

    The executable tiers above move real arrays; this spec is their analytic
    twin used by ``sim/`` to model the paper's DC/HC/MC design points as
    tier configurations (same contract, no jax arrays).
    """

    kind: str                          # device | host | pooled
    bw_per_device: float               # stash/fetch bytes/s per device
    shared_bw: float = 0.0             # host-side cap (0 = uncapped)
    uses_cpu: bool = False             # traffic counts against CPU memory BW
    capacity_bytes: float = float("inf")

    @property
    def is_oracle(self) -> bool:
        return self.kind == "device"

    def effective_bw(self, n_devices: int, n_sockets: int = 2) -> float:
        """Per-device bandwidth when ``n_devices`` stream concurrently —
        the paper's §I observation that shared host links divide."""
        if self.is_oracle:
            return float("inf")
        bw = self.bw_per_device
        if self.shared_bw > 0:
            bw = min(bw, self.shared_bw * n_sockets / max(n_devices, 1))
        return bw
