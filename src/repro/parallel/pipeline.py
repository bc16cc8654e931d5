"""Pod-axis pipeline parallelism as a *schedule registry* over the tier API.

The production mesh runs data-parallel over the 'pod' axis by default
(gradient all-reduce over DCN only — the paper's intra-node scope maps to
in-pod traffic, MPI/IB maps to DCN).  For models whose *state* exceeds one
pod even pooled, the pod axis can instead run a pipeline: each pod owns a
contiguous stage of layers and microbatches stream through via ``ppermute``
over DCN.

Schedules are registry-pluggable (like the serving scheduler and codec
registries) and differ in how a stage's saved activations are *placed*:

* ``gpipe`` — the classic schedule: every stage keeps all M microbatch
  activations implicitly live until its backward runs (peak activation
  memory grows with M).
* ``1f1b``  — one-forward-one-backward: in steady state a stage holds at
  most S in-flight microbatches; each stage input is routed through the
  :class:`~repro.core.tiers.PipelineStageTier` stash/fetch hooks
  (``MemoryRuntime.wrap_stage``, metered as ``act_stash``/``act_fetch``)
  instead of staying implicitly live, so device-resident activations are
  bounded by the in-flight window and the rest ride the pool.

Under SPMD autodiff both schedules execute the same forward tick loop
(T = M + S - 1 ticks, bubble fraction (S-1)/(M+S-1)); the schedule object
carries the placement policy (stash hooks) and the analytic contract
(``inflight``, ``bubble_fraction``) that ``core.policy.plan_memory`` and
``sim/`` trade against pool traffic.  Gradient accumulation is the
degenerate single-stage schedule (:func:`accumulate_microbatches`) — the
one microbatching code path ``train/loop.py`` uses.

``pipeline_apply`` is the generic combinator (stage_fn is any layer-stack
function, inputs may be pytrees); it is exercised by tests/test_pipeline.py
on a toy stack and wired into launch/train.py behind ``--pipeline``.
"""
from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any

# metrics accumulated as SUMS across microbatches; everything else is a mean
SUM_METRICS = ("tokens",)


# ---------------------------------------------------------------------------
class PipelineSchedule(abc.ABC):
    """One pipeline schedule: the tick loop + the activation-placement policy.

    ``runtime`` is the stage :class:`~repro.core.runtime.MemoryRuntime`
    (tier = :class:`~repro.core.tiers.PipelineStageTier`); schedules that
    stash route every stage input through it.  With ``runtime=None`` the
    schedule still runs — only the placement hooks are disabled — so the
    analytic contract (``inflight``/``bubble_fraction``) is usable without
    building tiers (``core.policy`` does exactly that).
    """

    name: str = "abstract"
    #: route stage inputs through the stage tier (vs implicitly live)
    stash_saved: bool = False

    def __init__(self, runtime=None):
        self.runtime = runtime

    # -- analytic contract (consumed by core.policy / sim) ------------------
    def inflight(self, n_stages: int, n_micro: int) -> int:
        """Max microbatch activations live on one stage at once."""
        return n_micro

    def bubble_fraction(self, n_stages: int, n_micro: int) -> float:
        """Idle fraction of the (M + S - 1)-tick schedule: (S-1)/(M+S-1)."""
        s, m = n_stages, n_micro
        return (s - 1) / (m + s - 1) if (m + s - 1) > 0 else 0.0

    # -- placement hooks ----------------------------------------------------
    def wrap_stage(self, stage_fn: Callable, name: str = "stage") -> Callable:
        if not self.stash_saved or self.runtime is None or \
                not self.runtime.offloads:
            return stage_fn
        return self.runtime.wrap_stage(stage_fn, name=name)

    # -- the degenerate single-stage path (outside shard_map) ---------------
    def run_local(self, stage_fn: Callable, stage_params: Pytree, x: Pytree,
                  n_micro: int) -> Pytree:
        """S=1 schedule on one device group: M microbatches scanned
        sequentially through the (possibly stash-wrapped) stage, so a
        planner-chosen ``n_micro`` still delivers its per-microbatch
        activation footprint without a stage mesh."""
        fn = self.wrap_stage(stage_fn, name=f"{self.name}_stage")
        M = max(1, n_micro)
        leaves = jax.tree_util.tree_leaves(x)
        if M <= 1 or not leaves or leaves[0].shape[0] % M:
            return fn(stage_params, x)
        micro = jax.tree.map(
            lambda l: l.reshape((M, l.shape[0] // M) + l.shape[1:]), x)

        def body(_, xm):
            return None, fn(stage_params, xm)

        _, outs = jax.lax.scan(body, None, micro)
        return jax.tree.map(lambda o, l: o.reshape(l.shape), outs, x)

    # -- the tick loop ------------------------------------------------------
    def run(self, stage_fn: Callable, stage_params: Pytree, x: Pytree,
            n_micro: int, axis_name: str = "pod") -> Pytree:
        """Run the schedule *inside shard_map* over ``axis_name``.

        stage_fn(params, x) -> y, applied by each member to its own stage;
        x may be a pytree of arrays sharing the leading (batch) dim — every
        member enters with the same x; member 0's stage consumes it first.

        With S stages and M microbatches the loop runs T = M + S - 1 ticks.
        At each tick a member runs its stage on the microbatch it received
        and passes the activation to the next member over DCN.  The SPMD
        emulation is *dense*: every member executes every tick, including
        its S-1 fill/drain ticks whose inputs are garbage (masked out of
        the output), so wall-clock and stash work scale with M + S - 1
        while the analytic contract prices exactly the M real microbatches.
        """
        S = jax.lax.axis_size(axis_name)
        fn = self.wrap_stage(stage_fn, name=f"{self.name}_stage")
        if S == 1:
            return fn(stage_params, x)
        me = jax.lax.axis_index(axis_name)
        M = n_micro
        leaves = jax.tree_util.tree_leaves(x)
        assert leaves and leaves[0].shape[0] % M == 0, \
            (M, [l.shape for l in leaves])
        micro = jax.tree.map(
            lambda l: l.reshape((M, l.shape[0] // M) + l.shape[1:]), x)
        perm = [(i, (i + 1) % S) for i in range(S)]

        T = M + S - 1
        buf = jax.tree.map(lambda l: jnp.zeros_like(l[0]), micro)
        outs = jax.tree.map(jnp.zeros_like, micro)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any); others use what arrived
            inject = jax.tree.map(lambda l: l[jnp.clip(t, 0, M - 1)], micro)
            x_in = jax.tree.map(lambda a, b: jnp.where(me == 0, a, b),
                                inject, buf)
            y = fn(stage_params, x_in)
            # last stage records its result for microbatch (t - (S-1))
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = jnp.logical_and(me == S - 1, t >= S - 1)
            outs = jax.tree.map(
                lambda o, yy: jnp.where(
                    write,
                    jax.lax.dynamic_update_index_in_dim(o, yy, out_idx, 0),
                    o),
                outs, y)
            buf = jax.tree.map(
                lambda l: jax.lax.ppermute(l, axis_name, perm), y)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # results live on the last stage; broadcast them to every member so
        # the caller sees a replicated output (loss is computed everywhere).
        outs = jax.tree.map(
            lambda o: jax.lax.psum(
                jnp.where(me == S - 1, o, jnp.zeros_like(o)), axis_name),
            outs)
        return jax.tree.map(lambda o, l: o.reshape(l.shape), outs, x)


class GPipeSchedule(PipelineSchedule):
    """GPipe: all-forward then all-backward; every stage holds all M
    microbatch activations implicitly live (zero pool traffic, peak
    activation memory grows with M)."""

    name = "gpipe"
    stash_saved = False


class OneFOneBSchedule(PipelineSchedule):
    """1F1B: steady-state in-flight activations bounded by min(S, M) per
    stage; stage inputs are stashed through the stage tier and fetched in
    backward (``act_stash``/``act_fetch`` in the traffic report)."""

    name = "1f1b"
    stash_saved = True

    def inflight(self, n_stages: int, n_micro: int) -> int:
        return min(n_stages, n_micro)


# ---------------------------------------------------------------------------
# schedule registry (mirrors the scheduler/codec registries)
_SCHEDULE_REGISTRY: Dict[str, Type[PipelineSchedule]] = {}


def register_schedule(name: str, cls: Type[PipelineSchedule]) -> None:
    _SCHEDULE_REGISTRY[name] = cls


def registered_schedules() -> Tuple[str, ...]:
    return tuple(sorted(_SCHEDULE_REGISTRY))


def get_schedule(name: str, runtime=None) -> PipelineSchedule:
    if name not in _SCHEDULE_REGISTRY:
        raise KeyError(f"unknown pipeline schedule {name!r}; "
                       f"registered: {registered_schedules()}")
    return _SCHEDULE_REGISTRY[name](runtime)


register_schedule("gpipe", GPipeSchedule)
register_schedule("1f1b", OneFOneBSchedule)


# ---------------------------------------------------------------------------
def pipeline_apply(stage_fn: Callable, stage_params: Pytree, x: Pytree,
                   n_micro: int, axis_name: str = "pod",
                   schedule: Union[str, PipelineSchedule] = "gpipe"
                   ) -> Pytree:
    """Run a pipeline schedule over ``axis_name`` *inside shard_map*."""
    if isinstance(schedule, str):
        schedule = get_schedule(schedule)
    return schedule.run(stage_fn, stage_params, x, n_micro, axis_name)


def make_pipelined(mesh: Mesh, stage_fn: Callable, n_micro: int,
                   axis_name: str = "pod",
                   stage_param_spec: Optional[P] = None,
                   schedule: Union[str, PipelineSchedule] = "gpipe",
                   runtime=None) -> Callable:
    """shard_map wrapper: (stacked stage params, x) -> y."""
    if stage_param_spec is None:
        stage_param_spec = P(axis_name)
    if isinstance(schedule, str):
        schedule = get_schedule(schedule, runtime=runtime)

    def inner(stage_params, x):
        sp = jax.tree.map(lambda l: l[0], stage_params)  # my stage (size-1)
        return schedule.run(stage_fn, sp, x, n_micro, axis_name)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(stage_param_spec, P()),
                         out_specs=P(),
                         check_vma=False)


# ---------------------------------------------------------------------------
def accumulate_microbatches(loss_fn: Callable, params: Pytree, batch: Pytree,
                            n_micro: int):
    """The degenerate single-stage schedule: gradient accumulation.

    Splits the batch's leading dim into ``n_micro`` microbatches scanned
    sequentially — the S=1, DCN-free corner of the schedule space (no
    bubble, no stage tier, activation memory divided by M).  Returns
    ``(grads, loss, metrics)`` with grads/loss averaged and *every* metric
    the loss_fn reports accumulated across microbatches: token counters
    (:data:`SUM_METRICS`) are summed, losses averaged.
    """
    n = n_micro

    def micro(i):
        return jax.tree.map(
            lambda v: v.reshape((n, v.shape[0] // n) + v.shape[1:])[i]
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] % n == 0 else v,
            batch)

    # metric keys are static: shape-infer them from one microbatch
    m0 = jax.eval_shape(lambda p, b: loss_fn(p, b)[1], params, micro(0))

    def body(carry, i):
        acc, msum, ltot = carry
        (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, micro(i))
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        msum = {k: msum[k] + jnp.float32(m[k]) for k in msum}
        return (acc, msum, ltot + l), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    zeros_m = {k: jnp.float32(0) for k in m0}
    (g, msum, ltot), _ = jax.lax.scan(
        body, (zeros, zeros_m, jnp.float32(0)), jnp.arange(n))
    g = jax.tree.map(lambda v: v / n, g)
    metrics = {k: (v if k in SUM_METRICS else v / n)
               for k, v in msum.items()}
    return g, ltot / n, metrics
