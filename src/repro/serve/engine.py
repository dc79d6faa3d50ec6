"""CHORDS serving runtimes: streaming early-exit sampling + two batching modes.

``StreamingSampler`` runs Algorithm 1 inside a single jitted ``while_loop``
that stops as soon as two consecutive streamed outputs agree within rtol
(paper Section 5 "diffusion streaming") — rounds not executed are wall-clock
saved. ``ChordsEngine`` is the *static-batch* server around it: queued
requests are padded to a fixed ``max_batch`` (one jit trace, ever) and the
batch is held until its slowest request converges.

``ContinuousEngine`` is the production runtime: a ``[S, K, ...]`` slot×core
grid where every engine round advances all live slots by one lockstep round,
an admission queue feeds free slots *every round* (masked in-place reset —
no retrace), finished slots drain immediately, and per-slot accept state
(rtol, init sequence from request priority, round counter) rides the jitted
``SlotState``. Requests therefore never queue behind a straggler in another
lane. See ``src/repro/serve/README.md`` for the slot lifecycle and S×K
sizing guidance.

Every compiled program — the slot round / admission / multi-round programs
and the streaming sampler's while_loop — is owned by a shared
:class:`repro.serve.executor.RoundExecutor` and cached per
:class:`~repro.serve.executor.GridSpec` / ``StreamSpec`` key; the engines
hold no private compile paths. That is also what makes the slot grid
**demand-paged**: ``ContinuousEngine(min_slots=..., max_slots=...)`` grows
and shrinks S along power-of-two capacity buckets (queue depth pages slots
in immediately; sustained low occupancy pages them out behind a hysteresis
window and a scheduling-policy veto), live lanes migrating between grids via
a bit-exact masked gather — a resize is a capacity change, never a result
change.

Admission ordering, deadline handling, preemption, and the resize veto live
in the ``repro.serve.sched`` policy layer (FIFO remains the default); the
multi-round device loop (``step(max_rounds_on_device=R)``) amortizes the
per-round done-flag readback when the grid is busy.

``ContinuousEngine(overlap=True)`` replaces the synchronous
admit → block → drain step with a **double-buffered async dispatch loop**:
while round R runs on device, the host computes round R+1's *speculative*
policy decision against the cost model's predicted post-R lane state and
enqueues the next dispatch immediately; the done-flag readback then either
*confirms* the speculation (the dispatch is already in flight — outputs
bitwise-identical to the synchronous path) or *reconciles* it (the
speculative admission is rolled back through the retained pre-decision
buffers + the same masked admission program; wasted device work is bounded
to the one in-flight round and counted in
``stats()['speculation_rollbacks']``). See the "async runtime" section of
serve/README.md.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chords import default_lane_profile
from repro.core.init_sequence import make_sequence
from repro.obs import NULL_TRACER, MetricsRegistry, Tracer
from repro.serve.executor import (GridSpec, RoundExecutor, SlotState,
                                  StreamSpec, ambient_sharding_tag)
from repro.serve.sched.cost import CostModel
from repro.serve.sched.policy import (Decision, EngineView, LaneView,
                                      ResizeProposal, get_policy)
from repro.serve.sched.queue import AdmissionQueue, QueueItem


@dataclasses.dataclass
class SampleOut:
    """Batched samplers carry per-request arrays in the scalar fields."""
    sample: jax.Array
    rounds_used: object  # int, or [B] array when batched
    accepted_core: object
    speedup: object
    latency_rounds: Optional[int] = None  # queue wait + compute (engines only)


def _resolve_executor(drift, tgrid, n_steps, executor,
                      use_kernel, tracer=None, metrics=None) -> RoundExecutor:
    """Engine-side executor setup: build one, or adopt the provided one.

    ``use_kernel=None`` (the engine default) inherits the executor's
    setting; an explicit bool that *contradicts* a provided executor raises
    instead of being silently ignored — the flag lives on the executor,
    which owns compilation. A shared executor keeps its own tracer/metrics
    (possibly the no-op defaults); only a freshly built one inherits the
    engine's.
    """
    if executor is None:
        return RoundExecutor(drift, tgrid, n_steps,
                             use_kernel=bool(use_kernel),
                             tracer=tracer, metrics=metrics)
    if use_kernel is not None and bool(use_kernel) != executor.use_kernel:
        raise ValueError(
            f"use_kernel={use_kernel} conflicts with the provided "
            f"executor's use_kernel={executor.use_kernel}; configure the "
            f"flag on the RoundExecutor itself")
    return executor


class StreamingSampler:
    """Early-exit CHORDS sampler.

    ``batched=True`` treats axis 0 of ``x0`` as independent requests: the
    rtol accept test, the accepted round, and the chosen core are tracked
    *per request*, and the lockstep loop runs until every request has
    converged (or all N rounds ran). A whole-batch norm would let one
    converged request accept the entire batch — and a single stiff request
    hold every other one hostage.

    ``sample(x0, live=...)`` masks out padding rows: dead rows are born
    pre-accepted so they can never extend the while_loop, which is what lets
    ``ChordsEngine`` pad partial batches to a fixed shape (single jit trace).

    The compiled program comes from the ``executor`` trace cache (built on
    demand when none is passed); ``use_kernel=True`` routes the fused Pallas
    step+rectify kernel into the round body, bitwise-identical outputs.
    """

    def __init__(self, drift, n_steps: int, num_cores: int, tgrid,
                 i_seq: Optional[Sequence[int]] = None, rtol: float = 0.05,
                 batched: bool = False,
                 executor: Optional[RoundExecutor] = None,
                 use_kernel: Optional[bool] = None):
        self.n = n_steps
        self.k = num_cores
        self.tgrid = tgrid
        self.i_seq = list(i_seq) if i_seq is not None else make_sequence(
            num_cores, n_steps)
        self.i_arr = jnp.asarray(self.i_seq, jnp.int32)
        self.rtol = rtol
        self.drift = drift
        self.batched = batched
        self.executor = _resolve_executor(drift, tgrid, n_steps, executor,
                                          use_kernel)
        self._jitted = self.executor.stream(StreamSpec(
            num_cores=num_cores, i_seq=tuple(self.i_seq), rtol=rtol,
            batched=batched, sharding=ambient_sharding_tag()))

    def sample(self, x0, live=None) -> SampleOut:
        req_shape = (x0.shape[0],) if self.batched else ()
        if live is None:
            live = jnp.ones(req_shape, bool)
        out, rounds, chosen = self._jitted(x0, live)
        if self.batched:
            rounds = np.asarray(rounds)
            return SampleOut(out, rounds, np.asarray(chosen),
                             self.n / np.maximum(1, rounds))
        rounds = int(rounds)
        return SampleOut(out, rounds, int(chosen), self.n / max(1, rounds))

    @property
    def num_traces(self) -> int:
        """Distinct jit traces so far (tests assert padding keeps this at 1).
        Falls back to 1 if the (private) jax cache probe ever disappears."""
        probe = getattr(self._jitted.jitted, "_cache_size", None)
        return int(probe()) if callable(probe) else 1


@dataclasses.dataclass
class Request:
    rid: int
    key: jax.Array
    cond: Optional[object] = None
    priority: int = 0  # higher = more aggressive init sequence (earlier exit)
    rtol: Optional[float] = None  # per-request accept tolerance
    deadline_rounds: Optional[int] = None  # SLA: finish within this many
    # lockstep rounds of submission (None = best-effort, never counted as a
    # miss); scheduling policies order/admit/preempt against it
    mode: str = "exact"  # lane mode the request OPTS INTO: "exact" (default,
    # bitwise-identical to the homogeneous engine), "adaptive" (stability-
    # gated step skipping), or "draft" (skipping + coarse draft lanes).
    # Honored only when the engine was built with a lane_profile; the policy
    # may still upgrade a non-exact request to exact when its deadline allows


class ChordsEngine:
    """Static-batch request server around the streaming sampler.

    A batch is held until its *slowest* request converges — the baseline the
    continuous-batching runtime is measured against. Partial batches are
    padded to ``max_batch`` with a live-mask so every call hits the same jit
    trace (``sampler.num_traces == 1`` no matter the arrival pattern).
    """

    def __init__(self, drift_builder: Callable, latent_shape: tuple,
                 n_steps: int, num_cores: int, tgrid, max_batch: int = 8,
                 rtol: float = 0.05,
                 executor: Optional[RoundExecutor] = None,
                 use_kernel: Optional[bool] = None):
        self.latent_shape = latent_shape
        self.max_batch = max_batch
        self.drift_builder = drift_builder
        self.sampler = StreamingSampler(drift_builder, n_steps, num_cores,
                                        tgrid, rtol=rtol, batched=True,
                                        executor=executor,
                                        use_kernel=use_kernel)
        self.executor = self.sampler.executor
        self.queue: list[Request] = []
        self.stats = []

    def submit(self, req: Request):
        self.queue.append(req)

    def step(self) -> list[tuple[int, SampleOut]]:
        """Serve one batch from the queue; returns [(rid, SampleOut)]."""
        if not self.queue:
            return []
        batch, self.queue = self.queue[: self.max_batch], self.queue[self.max_batch:]
        pad = self.max_batch - len(batch)
        keys = jnp.stack([r.key for r in batch] + [batch[0].key] * pad)
        noise = jax.vmap(
            lambda kk: jax.random.normal(kk, self.latent_shape))(keys)
        live = jnp.asarray([True] * len(batch) + [False] * pad)
        t0 = time.perf_counter()
        out = self.sampler.sample(noise, live=live)
        dt = time.perf_counter() - t0
        # the lockstep loop runs until the *slowest* request converges; the
        # batch's wall-clock rounds is therefore the per-request max
        real = np.arange(len(batch))
        self.stats.append({"batch": len(batch), "padded": pad,
                           "rounds": int(np.max(out.rounds_used[real])),
                           "speedup": float(np.min(out.speedup[real])),
                           "wall_s": dt})
        return [(r.rid, SampleOut(out.sample[i], int(out.rounds_used[i]),
                                  int(out.accepted_core[i]),
                                  float(out.speedup[i])))
                for i, r in enumerate(batch)]

    def total_rounds(self) -> int:
        """Rounds-to-drain: static batches run back-to-back."""
        return int(sum(s["rounds"] for s in self.stats))


@dataclasses.dataclass
class _DecisionUndo:
    """Host-side inverse of one speculatively applied :class:`Decision`.

    The device side of a rollback is trivial — the engine just reinstates
    the retained pre-decision ``SlotState`` (``admit`` is never donated, so
    those buffers stay readable). This record undoes the *host* effects:
    queue membership, preemption credit/counters, and the per-slot mirrors.
    """

    admissions: List[tuple]          # (slot, item) admitted -> re-queue
    evictions: List[tuple]           # (slot, item, ran) evicted -> restore
    prior: Dict[int, tuple]          # slot -> mirror tuple before the decision
    preempted_new: List[int]         # rids first marked preempted here


def bucket_ladder(min_slots: int, max_slots: int) -> List[int]:
    """Power-of-two capacity buckets from ``min_slots`` up to ``max_slots``
    (the top bucket is clamped to ``max_slots`` even off-ladder)."""
    if min_slots < 1 or min_slots > max_slots:
        raise ValueError(f"need 1 <= min_slots <= max_slots, got "
                         f"{min_slots}..{max_slots}")
    b, out = min_slots, [min_slots]
    while b < max_slots:
        b = min(b * 2, max_slots)
        out.append(b)
    return out


class ContinuousEngine:
    """Continuous-batching CHORDS runtime over a demand-paged [S, K, ...]
    slot grid.

    Every ``step()``: (0) with elastic capacity enabled, maybe resize the
    grid (see below); (1) ask the scheduling ``policy`` which queued requests
    to admit into which slots — and, for a preemptive policy, which in-flight
    lanes to evict first — then apply the decision with the masked in-place
    admission program (no retrace, untouched lanes bit-identical);
    (2) run the lockstep round for all live slots inside a single jitted
    call — or, with ``step(max_rounds_on_device=R)``, up to R rounds inside
    one ``lax.while_loop`` that returns early the moment any slot's accept
    fires, so a busy grid pays ONE host sync per R rounds instead of one per
    round (the ``host_syncs`` counter tracks exactly these done-flag
    readbacks); (3) drain slots whose accept fired. A request's output is
    identical whether its slot is fresh, recycled, or migrated, and a slot
    running K==1 degenerates to the sequential solver (tested invariants).

    **Elastic capacity** (``min_slots < max_slots``): S moves along the
    power-of-two bucket ladder. Growth is immediate — whenever queued demand
    exceeds free capacity, S jumps to the smallest bucket that fits
    ``live + queued`` (policies cannot veto growth). Shrinking is
    hysteresis-gated: only after occupancy has fit the next bucket down for
    ``resize_hysteresis`` consecutive lockstep rounds, and only if the
    policy does not veto (``Policy.consider_resize`` — EDF
    policies veto a shrink that would push a queued deadline into a
    predicted miss). Live lanes migrate to the new grid via a masked gather
    that copies each lane's carry bit-exactly, so a resize never changes any
    request's output. With ``min_slots == max_slots`` (the default) every
    resize path is dead code and behavior is bit-for-bit the fixed-S engine.

    All compiled programs come from the ``executor`` trace cache: one
    compile per distinct ``GridSpec`` (capacity bucket) ever touched, cache
    hits on re-entry — ``stats()['retraces']`` is bounded by the number of
    distinct buckets visited.

    ``policy`` is ``'fifo'`` (default, the original submission-order
    behavior), ``'edf'``, ``'edf-preempt'``, or any
    ``repro.serve.sched.Policy`` instance. Deadlines (``Request.
    deadline_rounds``) are relative to submission, in lockstep-round units;
    ``stats()`` reports the miss rate over requests that declared one.

    ``num_cores`` is K for every slot. On a mesh, size S to the 'data' axis
    (slots shard over it under ``use_sharding``) and K× the per-slot latent
    to what one shard's HBM holds — see serve/README.md.

    **Heterogeneous lanes** (``lane_profile=...``): the K cores of every
    slot become asymmetric — trailing cores take a *draft* role (drift
    evaluated through a coarse down/up-sample pair) and/or a per-core
    stability-gated *step-skip* eligibility (see
    ``core.chords.LaneSpec`` / ``default_lane_profile``). Requests opt in
    per-request via ``Request.mode`` ("exact" | "adaptive" | "draft");
    the cost model prices each mode from its observed skip rate and the
    policy may upgrade a non-exact request to exact when its deadline
    allows. ``mode="exact"`` lanes zero every gate, so their outputs are
    bitwise-identical to the homogeneous engine; ``lane_profile=None``
    (the default) compiles the exact same programs as before.

    **Async overlap** (``overlap=True``): ``step()`` becomes the
    double-buffered dispatch loop described in the module docstring — the
    host never blocks on a round it has not already replaced with the next
    dispatch. With exact predictions (``rtol=0``: the force-accept round is
    closed-form) every speculation confirms and the run is bitwise-identical
    to ``overlap=False`` on the same trace; mispredictions are reconciled by
    rolling the speculative admission back (bounded, counted — see
    ``stats()['speculation_rollbacks']``). The synchronous mode is the
    default and its behavior is unchanged.
    """

    def __init__(self, drift: Callable, latent_shape: tuple, n_steps: int,
                 num_cores: int, tgrid, num_slots: int = 4, rtol: float = 0.05,
                 priority_speedup: float = 1.25, policy=None,
                 aging_rounds: int = 32,
                 min_slots: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 resize_hysteresis: int = 8,
                 overlap: bool = False,
                 lane_profile=None,
                 lane_skip_tau: float = 0.4,
                 executor: Optional[RoundExecutor] = None,
                 use_kernel: Optional[bool] = None,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.latent_shape = tuple(latent_shape)
        self.n = n_steps
        self.k = num_cores
        self.rtol = rtol
        self.priority_speedup = priority_speedup
        # heterogeneous lanes: a lane_profile makes the K cores asymmetric
        # (draft vs refine roles, per-core skip eligibility — see
        # core.chords.LaneSpec). "default"/True resolves the standard
        # profile for K; None keeps the homogeneous engine (every request
        # runs exact, Request.mode is ignored, programs/jaxprs unchanged)
        if lane_profile is True or lane_profile == "default":
            lane_profile = default_lane_profile(num_cores)
        self.lane_profile = tuple(lane_profile) if lane_profile else None
        self.lane_skip_tau = float(lane_skip_tau)
        # observability: NULL_TRACER is a zero-allocation no-op, so the
        # un-traced engine stays bitwise-identical to pre-obs behavior;
        # the metrics registry is the single source of truth behind stats()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.policy = get_policy(policy)
        self.cost = CostModel(num_cores, n_steps,
                              priority_speedup=priority_speedup,
                              metrics=self.metrics)
        self.executor = _resolve_executor(drift, tgrid, n_steps, executor,
                                          use_kernel, tracer=self.tracer,
                                          metrics=self.metrics)
        if min_slots is None and max_slots is None:
            self.min_slots = self.max_slots = int(num_slots)
        else:
            self.min_slots = int(min_slots if min_slots is not None
                                 else num_slots)
            self.max_slots = int(max_slots if max_slots is not None
                                 else max(num_slots, self.min_slots))
        self._ladder = bucket_ladder(self.min_slots, self.max_slots)
        # the trace cache must hold every capacity bucket (on top of what
        # other engines sharing this executor already cached), or ladder
        # re-entry would evict-and-retrace — breaking the retraces <=
        # distinct-buckets contract
        self.executor.reserve_grid_capacity(len(self._ladder))
        self.resize_hysteresis = max(1, int(resize_hysteresis))
        self._install_grid(self._ladder[0])  # demand-paged: start smallest
        self._buckets_visited = {self.s}
        self.queue = AdmissionQueue(aging_rounds=aging_rounds)
        self.round_count = 0  # plain attribute: benchmark drivers write it
        self.preempted_rids: set = set()
        self.migrated_rids: set = set()  # rids whose lane crossed a resize
        self._low_streak = 0    # consecutive rounds of shrinkable occupancy
        self.overlap = bool(overlap)
        # every scalar that used to live in an ad-hoc attribute is now a
        # registry instrument under a stable dotted name (stats() renders
        # the same legacy keys from these; obs check reads them from the
        # trace's embedded snapshot)
        m = self.metrics
        self._c_host_syncs = m.counter("serve.host_syncs")
        self._c_preempt = m.counter("serve.preempt.count")
        self._c_preempt_wasted = m.counter("serve.preempt.rounds_wasted")
        self._c_deadline_total = m.counter("serve.deadline.total")
        self._c_deadline_misses = m.counter("serve.deadline.misses")
        self._c_live = m.counter("serve.occupancy.live_rounds")
        self._c_slot_rounds = m.counter("serve.occupancy.slot_rounds")
        self._c_wasted = m.counter("serve.occupancy.wasted_rounds")
        self._c_resizes = m.counter("serve.resize.count")
        self._c_grows = m.counter("serve.resize.grows")
        self._c_shrinks = m.counter("serve.resize.shrinks")
        self._c_vetoes = m.counter("serve.resize.vetoes")
        self._c_migrations = m.counter("serve.resize.migrations")
        self._c_served = m.counter("serve.served")
        self._c_spec = m.counter("serve.spec.count")
        self._c_spec_confirms = m.counter("serve.spec.confirms")
        self._c_spec_rollbacks = m.counter("serve.spec.rollbacks")
        self._c_spec_wasted = m.counter("serve.spec.rounds_wasted")
        self._c_drain_lag = m.counter("serve.drain_lag_rounds")
        self._c_dispatches = m.counter("serve.dispatches")
        # heterogeneous-lane accounting (all zero on a homogeneous grid)
        self._c_lane_skips = m.counter("serve.lanes.skips")
        self._c_lane_nonexact = m.counter("serve.lanes.served_nonexact")
        self._c_lane_promotes = m.counter("serve.lanes.promotes")
        # bounded reservoirs replace the previously unbounded _latencies /
        # _speedups lists: count/sum/min/max stay exact forever, percentiles
        # are exact up to the reservoir capacity and an unbiased uniform-
        # sample estimate beyond (see obs/metrics.py docstring)
        self._h_latency = m.histogram("serve.latency_rounds")
        self._h_speedup = m.histogram("serve.speedup")
        # round-gap timer: host-side monotonic gap between consecutive device
        # dispatches while the grid stays busy — the device-starvation metric
        # the async loop exists to drive to ~0 (both modes measure it)
        self._h_gap = m.histogram("serve.round_gap_s")
        m.gauge("serve.overlap").set(float(self.overlap))
        self._last_dispatch_done: Optional[float] = None
        self._disp_phase = None  # the open dispatch/* phase (tracing on)
        self._disp_live = 0
        self._submit_wall: Dict[int, float] = {}  # rid -> queued-span start

    # -- grid management ------------------------------------------------------

    def _spec(self, s: int) -> GridSpec:
        # the ambient mesh context is part of the cache key: a program
        # traced under use_sharding must never be served to a bare engine.
        # donate=True: stepping the grid reuses the old state's buffers
        # (both modes — the async double buffer must not double memory,
        # and the sync loop never re-reads a superseded state either)
        return GridSpec(num_slots=s, num_cores=self.k,
                        latent_shape=self.latent_shape,
                        sharding=ambient_sharding_tag(),
                        donate=True,
                        lane_profile=self.lane_profile)

    def _install_grid(self, s: int):
        """Fresh grid at capacity ``s`` (construction / empty resize)."""
        self.s = s
        self.spec = self._spec(s)
        self._prog = self.executor.grid(self.spec)
        self.state = self._prog.init_state()
        self._slot_item: List[Optional[QueueItem]] = [None] * s
        self._slot_iseq: List[Optional[list]] = [None] * s
        self._slot_rtol = np.full((s,), self.rtol, np.float32)  # host mirror
        self._admit_round: List[int] = [0] * s
        # cost-model prediction of the absolute round each lane accepts —
        # the async engine's speculation horizon (None = slot free)
        self._pred_done: List[Optional[int]] = [None] * s
        # wall clock of each lane's committed admission — the start of its
        # request/compute span on the per-slot trace track
        self._admit_wall: List[float] = [0.0] * s
        # lane mode each slot's resident request runs under (meaningful
        # only while the slot is occupied; admissions overwrite it)
        self._slot_mode: List[str] = ["exact"] * s
        self.metrics.gauge("serve.slots").set(float(s))
        if self.tracer.enabled:
            suffix = ""
            if self.lane_profile is not None:
                # role-suffixed labels: D=draft, A=skip-only, R=refine —
                # same letters enumerate_programs tags hetero grids with
                roles = "".join(
                    "D" if sp.role == "draft" else
                    ("A" if sp.skip else "R") for sp in self.lane_profile)
                suffix = f" [{roles}]"
            for i in range(s):
                self.tracer.label_track(("slots", i), f"slot {i}{suffix}")

    def _resize_to(self, new_s: int):
        """Move the grid to capacity ``new_s``, migrating live lanes.

        Migration is a masked row gather (``executor.migrate``): every
        migrated lane's carry + accept state is copied bit-exactly into the
        lowest-indexed destination lanes, so in-flight requests cannot
        observe the resize.
        """
        occupied = [i for i, it in enumerate(self._slot_item)
                    if it is not None]
        assert len(occupied) <= new_s, (occupied, new_s)
        old_s, old_spec, old_state = self.s, self.spec, self.state
        old = (self._slot_item, self._slot_iseq, self._slot_rtol,
               self._admit_round, self._pred_done, self._admit_wall,
               self._slot_mode)
        t_mig = self.tracer.now()
        self._install_grid(new_s)
        if occupied:
            mask = np.zeros((new_s,), bool)
            src = np.zeros((new_s,), np.int32)
            for dst, s_old in enumerate(occupied):
                mask[dst], src[dst] = True, s_old
                self._slot_item[dst] = old[0][s_old]
                self._slot_iseq[dst] = old[1][s_old]
                self._slot_rtol[dst] = old[2][s_old]
                self._admit_round[dst] = old[3][s_old]
                self._pred_done[dst] = old[4][s_old]
                self._slot_mode[dst] = old[6][s_old]
                self.migrated_rids.add(old[0][s_old].payload.rid)
                # a migration ends the lane's residency on the old slot
                # track and opens a new one on the destination — per-slot
                # compute spans stay nest-or-disjoint across renumbering
                self.tracer.span("request/compute", old[5][s_old],
                                 round_idx=self.round_count,
                                 track=("slots", s_old), t1=t_mig,
                                 rid=old[0][s_old].payload.rid,
                                 migrated=True)
                self._admit_wall[dst] = t_mig
            self._c_migrations.inc(len(occupied))
            with self.tracer.phase("dispatch/migrate",
                                   round_idx=self.round_count,
                                   lanes=len(occupied)):
                self.state = self.executor.migrate(old_spec, self.spec)(
                    self.state, old_state, jnp.asarray(mask),
                    jnp.asarray(src))
            self.tracer.instant("migrate/lanes", round_idx=self.round_count,
                                lanes=len(occupied), src=old_s, dst=new_s)
        self._c_resizes.inc()
        self.tracer.instant("resize/grow" if new_s > old_s else
                            "resize/shrink", round_idx=self.round_count,
                            src=old_s, dst=new_s, live=len(occupied))
        self._buckets_visited.add(new_s)

    def _next_lower_bucket(self) -> Optional[int]:
        i = self._ladder.index(self.s)
        return self._ladder[i - 1] if i > 0 else None

    def _maybe_resize(self):
        """Demand paging: grow on queued demand, shrink on sustained idle."""
        if self.min_slots == self.max_slots:
            return
        live_ct = sum(it is not None for it in self._slot_item)
        if len(self.queue) > self.s - live_ct and self.s < self.max_slots:
            demand = live_ct + len(self.queue)
            target = self.s
            for b in self._ladder:
                if b > self.s:
                    target = b
                    if b >= demand:
                        break
            self._resize_to(target)  # growth is never vetoed
            self._c_grows.inc()
            self._low_streak = 0
            return
        lower = self._next_lower_bucket()
        if lower is None or live_ct > lower \
                or self._low_streak < self.resize_hysteresis:
            return
        # queued work does NOT block the proposal — whether the smaller
        # grid can still serve it (deadlines included) is the policy's call
        proposal = ResizeProposal(current_slots=self.s, new_slots=lower,
                                  live_lanes=live_ct, queued=len(self.queue))
        view = EngineView(now=self.round_count, queue=self.queue,
                          free_slots=[i for i, it in
                                      enumerate(self._slot_item)
                                      if it is None],
                          lanes=self._lane_views(), cost=self.cost,
                          lane_modes=self.lane_profile is not None)
        if self.policy.consider_resize(view, proposal) is None:
            self._c_vetoes.inc()
            self.tracer.instant("resize/veto", round_idx=self.round_count,
                                src=self.s, dst=lower, live=live_ct,
                                queued=len(self.queue))
            self._low_streak = 0  # re-arm: ask again after a full window
            return
        self._resize_to(lower)
        self._c_shrinks.inc()
        self._low_streak = 0

    # -- host loop ------------------------------------------------------------

    def _i_seq_for(self, priority: int) -> list:
        """Priority -> init sequence (the cost model's shared ladder)."""
        return self.cost.seq_for_level(priority)

    @property
    def has_inflight(self) -> bool:
        """Any slot occupied (queued requests not included)."""
        return any(it is not None for it in self._slot_item)

    @property
    def host_syncs(self) -> int:
        """Done-flag readbacks (the per-round sync killed by the
        multi-round device loop); a read view over ``serve.host_syncs``."""
        return int(self._c_host_syncs.value)

    def submit(self, req: Request):
        self.queue.submit(req, priority=req.priority,
                          submit_round=self.round_count,
                          deadline_rounds=req.deadline_rounds,
                          rtol=self.rtol if req.rtol is None else req.rtol)
        if self.tracer.enabled:
            self._submit_wall[req.rid] = self.tracer.now()
            self.tracer.instant("request/submit", round_idx=self.round_count,
                                track=("requests", req.rid), rid=req.rid,
                                priority=req.priority)

    def _lane_views(self) -> list[LaneView]:
        """Host-side in-flight snapshot — NO device sync: every live lane
        advances exactly the engine's round delta, so progress is
        ``round_count - admit_round``. ``invested`` additionally carries the
        rounds a previously preempted request already burned
        (``rounds_credit``) — victim ranking must weigh total sunk compute,
        while ``est_remaining`` must NOT (a re-admitted lane restarts from
        fresh noise, so credited rounds never reduce remaining work)."""
        lanes = []
        for slot, item in enumerate(self._slot_item):
            if item is None:
                continue
            done_r = self.round_count - self._admit_round[slot]
            lanes.append(LaneView(
                slot=slot, item=item, rounds_done=done_r,
                est_remaining=self.cost.remaining_rounds(
                    self._slot_iseq[slot], done_r, item.rtol,
                    mode=self._slot_mode[slot]),
                invested=done_r + item.rounds_credit))
        return lanes

    def _apply_decision(self, dec: Decision, now: Optional[int] = None,
                        record_undo: bool = False
                        ) -> Optional[_DecisionUndo]:
        """Apply a policy decision (evictions, then admissions) at round
        ``now`` (default: the current round).

        Admission init noise is generated *on device* inside the admit
        program from the stacked request keys — the host never materializes
        x0, so an admission batch costs zero device<->host latent transfers
        (it used to pay a d2h normal + re-upload per admission).

        ``record_undo=True`` returns a :class:`_DecisionUndo` that reverses
        every host-side effect — the async engine applies decisions
        *speculatively* and must be able to reconcile a misprediction.
        """
        now = self.round_count if now is None else now
        adm_slots = {a.slot for a in dec.admissions}
        assert all(s in adm_slots for s in dec.evictions), \
            (dec.evictions, adm_slots)  # eviction exists only to admit
        undo = _DecisionUndo([], [], {}, []) if record_undo else None
        if record_undo:
            for slot in set(dec.evictions) | adm_slots:
                undo.prior[slot] = (
                    self._slot_item[slot], self._slot_iseq[slot],
                    float(self._slot_rtol[slot]), self._admit_round[slot],
                    self._pred_done[slot], self._admit_wall[slot],
                    self._slot_mode[slot])
        for slot in dec.evictions:
            item = self._slot_item[slot]
            ran = now - self._admit_round[slot]
            item.rounds_credit += ran
            item.preemptions += 1
            self._c_preempt.inc()
            self._c_preempt_wasted.inc(ran)
            if record_undo:
                undo.evictions.append((slot, item, ran))
                if item.payload.rid not in self.preempted_rids:
                    undo.preempted_new.append(item.payload.rid)
            else:
                self._trace_evict(slot, item, ran, now,
                                  self._admit_wall[slot])
            self.preempted_rids.add(item.payload.rid)
            self._slot_item[slot] = None
            self._pred_done[slot] = None
            self.queue.push(item)  # submit round/deadline/credit preserved
        if not dec.admissions:
            return undo
        # the host-side admission build (mirrors, key and mask arrays, the
        # eager key stack) and the admit program, as one phase
        with self.tracer.phase("dispatch/admit", round_idx=now,
                               lanes=len(dec.admissions)):
            mask = np.zeros(self.s, bool)
            i_arr = np.zeros((self.s, self.k), np.int32)
            wall = self.tracer.now()
            hetero = self.lane_profile is not None
            for a in dec.admissions:
                mask[a.slot] = True
                i_arr[a.slot] = a.i_seq
                self._slot_rtol[a.slot] = a.item.rtol
                self._slot_item[a.slot] = a.item
                self._slot_iseq[a.slot] = list(a.i_seq)
                self._admit_round[a.slot] = now
                self._admit_wall[a.slot] = wall
                # the effective mode is the policy's Admission.mode, but only a
                # lane-profile engine can honor it — a homogeneous grid has no
                # draft/skip machinery, so everything runs (and is priced) exact
                mode = a.mode if hetero else "exact"
                self._slot_mode[a.slot] = mode
                self._pred_done[a.slot] = self.cost.predict_done_round(
                    a.i_seq, a.item.rtol, now, mode=mode)
                if record_undo:
                    undo.admissions.append((a.slot, a.item))
                else:
                    self._trace_admit(a.slot, a.item, now, wall)
            idx = np.asarray([a.slot for a in dec.admissions], np.int32)
            kstack = jnp.stack([jnp.asarray(a.item.payload.key)
                                for a in dec.admissions]).astype(jnp.uint32)
            keys = jnp.zeros((self.s, 2), jnp.uint32).at[idx].set(kstack)
            if hetero:
                # per-slot lane gates derived from the admitted mode: draft
                # lanes smooth only in "draft"; skipping arms in both non-exact
                # modes. An "exact" admission zeroes both gates, which makes
                # every lane-masked select pick the exact operand bitwise.
                draft_on = np.zeros((self.s,), bool)
                skip_tau = np.zeros((self.s,), np.float32)
                for a in dec.admissions:
                    m_eff = self._slot_mode[a.slot]
                    draft_on[a.slot] = m_eff == "draft"
                    skip_tau[a.slot] = (self.lane_skip_tau
                                        if m_eff in ("draft", "adaptive")
                                        else 0.0)
                self.state = self._prog.admit(
                    self.state, jnp.asarray(mask), keys, jnp.asarray(i_arr),
                    jnp.asarray(self._slot_rtol), jnp.asarray(draft_on),
                    jnp.asarray(skip_tau))
            else:
                self.state = self._prog.admit(self.state, jnp.asarray(mask),
                                              keys, jnp.asarray(i_arr),
                                              jnp.asarray(self._slot_rtol))
        return undo

    # -- commit-point trace emission ------------------------------------------
    # Speculatively applied decisions emit NOTHING (record_undo=True); their
    # events are emitted at confirmation (:meth:`_trace_commit_undo`) or by
    # the committed re-decide after a rollback — so a rolled-back admission
    # can never leave phantom lifecycle events in the trace, and per-track
    # spans stay well-nested by construction.

    def _trace_admit(self, slot: int, item: QueueItem, now: int,
                     wall: float) -> None:
        """Close the request's queued span and (re)open its residency."""
        self._admit_wall[slot] = wall
        if not self.tracer.enabled:
            return
        rid = item.payload.rid
        t_q = self._submit_wall.pop(rid, None)
        if t_q is not None:
            self.tracer.span("request/queued", t_q, round_idx=now,
                             track=("requests", rid), t1=wall, rid=rid,
                             slot=slot)

    def _trace_evict(self, slot: int, item: QueueItem, ran: int, now: int,
                     admit_wall: float) -> None:
        """A committed eviction ends the residency span and re-opens the
        request's queued span (evict-requeue)."""
        if not self.tracer.enabled:
            return
        rid = item.payload.rid
        wall = self.tracer.now()
        self.tracer.span("request/compute", admit_wall, round_idx=now,
                         track=("slots", slot), t1=wall, rid=rid,
                         preempted=True, rounds_ran=ran)
        self.tracer.instant("preempt", round_idx=now, rid=rid, slot=slot,
                            rounds_ran=ran)
        self._submit_wall[rid] = wall

    def _trace_commit_undo(self, undo: Optional[_DecisionUndo],
                           now: int) -> None:
        """Emit the lifecycle events of a speculative decision the verify
        readback just CONFIRMED. Called after the due drains so the evicted/
        replaced residents' spans close before the new residents' open."""
        if undo is None or not self.tracer.enabled:
            return
        for slot, item, ran in undo.evictions:
            prior = undo.prior[slot]
            self._trace_evict(slot, item, ran, now, prior[5])
        wall = self.tracer.now()
        for slot, item in undo.admissions:
            self._trace_admit(slot, item, now, wall)

    def _undo_decision(self, undo: _DecisionUndo):
        """Reverse the host side of a speculatively applied decision (the
        device side is the caller reinstating the retained pre-decision
        state). Queue ordering is key-computed at every pop, so the
        push/remove round-trips cannot perturb the survivors' order."""
        for _slot, item in undo.admissions:
            self.queue.push(item)  # popped by policy.decide: re-enqueue
        for _slot, item, ran in undo.evictions:
            self.queue.remove(item)
            item.rounds_credit -= ran
            item.preemptions -= 1
            self._c_preempt.inc(-1)  # negative inc: speculative-undo path
            self._c_preempt_wasted.inc(-ran)
        for rid in undo.preempted_new:
            self.preempted_rids.discard(rid)
        for slot, prior in undo.prior.items():
            (self._slot_item[slot], self._slot_iseq[slot], rtol,
             self._admit_round[slot], self._pred_done[slot],
             self._admit_wall[slot], self._slot_mode[slot]) = prior
            self._slot_rtol[slot] = rtol

    def _amortizable(self) -> bool:
        """May the host stay away for several rounds? Yes when nothing it
        could do between rounds matters: the queue is empty, or every slot
        is busy and the policy never preempts (then the next admission
        opportunity IS the next accept, which exits the device loop)."""
        if len(self.queue) == 0:
            return True
        if self.policy.preemptive:
            return False  # preemption decisions are made between rounds
        return not any(it is None for it in self._slot_item)

    # -- round-gap timer ------------------------------------------------------

    def _mark_dispatch(self, kind: str = "round", rounds: int = 1,
                       live: int = 0):
        """Called immediately BEFORE handing a round program to the device:
        records the host-side monotonic gap since the previous dispatch
        returned. On a busy grid this gap is exactly the time the device
        sat idle waiting for the host (decision + readback) — the async
        loop exists to drive it to ~0 (asserted by --serve-burst and
        machine-verified from the trace by ``repro.obs check``)."""
        t = time.monotonic()
        g = None
        if self._last_dispatch_done is not None:
            g = max(0.0, t - self._last_dispatch_done)
            self._h_gap.observe(g)
        self._c_dispatches.inc()
        if self.tracer.enabled:
            # each dispatch span carries its own measured busy-grid gap, so
            # the round-gap contract is checkable from the trace alone
            args = {"rounds": int(rounds), "live": int(live)}
            if g is not None:
                args["gap_s"] = g
            self._disp_live = int(live)
            self._disp_phase = self.tracer.phase(
                f"dispatch/{kind}", round_idx=self.round_count, **args)
            self._disp_phase.__enter__()

    def _dispatch_done(self):
        """Called immediately AFTER the dispatch call returns (jax dispatch
        is async: the call returns once the work is enqueued, which is the
        moment the device stops needing the host)."""
        self._last_dispatch_done = time.monotonic()
        if self.tracer.enabled:
            self._disp_phase.__exit__(None, None, None)
            self._disp_phase = None
            self.tracer.counter("occupancy", self._disp_live)
            self.tracer.counter("queue_depth", len(self.queue))

    # -- shared step pieces ---------------------------------------------------

    def _update_streak(self, live_before: int, live_after: int, ran: int):
        """Shrink hysteresis in DEVICE-ROUND units for both host paths.

        ``ran`` device rounds are credited when occupancy fit the next
        bucket down for the whole step (``live_before`` — post-admission —
        and ``live_after`` — post-drain — both within the lower bucket).
        A step during which occupancy *dropped* into range credits exactly
        ONE round regardless of ``ran``: the multi-round device loop exits
        on the accept that freed the lane, so precisely the final round of
        the chunk ended at the lower occupancy. (It used to credit the
        whole ``ran``, so a k-round step banked k rounds of hysteresis off
        a single low-occupancy round — elastic shrink timing silently
        depended on ``max_rounds_on_device``.)
        """
        lower = self._next_lower_bucket()
        if lower is None or live_after > lower:
            self._low_streak = 0
        elif live_before <= lower:
            self._low_streak += ran
        elif ran > 0:
            # any earlier streak was already zeroed while occupancy sat
            # above the bucket, so assignment == increment here
            self._low_streak = 1
        # ran == 0 (an async verify-only step): no round ran — unchanged

    def _finish_lane(self, item: QueueItem, i_seq, ru: int, chosen_k: int,
                     sample, acc_round: int, slot: int = -1,
                     admit_wall: float = 0.0, mode: str = "exact",
                     skips: int = 0) -> tuple[int, SampleOut]:
        """Account one drained lane. ``acc_round`` is the absolute engine
        round at which the accept fired — equal to ``round_count`` at the
        drain in the synchronous engine, and ``admit_round + rounds_used``
        always (the async engine uses the latter so latency/deadline numbers
        are identical no matter when the host *discovers* the accept).

        This drain commit is the ONLY place lane-mode trace instants
        (``lane/skip``, ``lane/promote``) are emitted — a rolled-back
        speculative step can therefore never leave phantom lane events
        (machine-checked by the obs 'lane-commit' pass)."""
        # queue wait is measured from SUBMIT time — eviction/re-admission
        # cycles and queue reordering all land in the same number
        latency = acc_round - item.submit_round
        missed = False
        if math.isfinite(item.deadline_round):
            missed = acc_round > item.deadline_round
            self._c_deadline_total.inc()
            self._c_deadline_misses.inc(int(missed))
        res = SampleOut(sample=sample, rounds_used=ru,
                        accepted_core=chosen_k,
                        speedup=self.n / max(1, ru),
                        latency_rounds=latency)
        # item.rtol (not the float32 device mirror) so the table key
        # matches the one predictions are queried with
        self.cost.observe_accept(i_seq, item.rtol, ru, mode=mode)
        self.cost.observe_skips(mode, skips, ru)
        self._c_served.inc()
        self._c_lane_skips.inc(skips)
        promoted = (self.lane_profile is not None
                    and 0 <= chosen_k < len(self.lane_profile)
                    and self.lane_profile[chosen_k].role == "draft")
        if mode != "exact":
            self._c_lane_nonexact.inc()
        if promoted:
            self._c_lane_promotes.inc()
        self._h_latency.observe(latency)
        self._h_speedup.observe(res.speedup)
        if self.tracer.enabled:
            rid = item.payload.rid
            self.tracer.span("request/compute", admit_wall,
                             round_idx=acc_round, track=("slots", slot),
                             rid=rid, rounds_used=ru, core=chosen_k,
                             latency_rounds=latency)
            if skips > 0:
                self.tracer.instant("lane/skip", round_idx=acc_round,
                                    track=("slots", slot), rid=rid,
                                    count=skips, mode=mode)
            if promoted:
                self.tracer.instant("lane/promote", round_idx=acc_round,
                                    track=("slots", slot), rid=rid,
                                    core=chosen_k, mode=mode)
            if missed:
                self.tracer.instant("deadline/miss", round_idx=acc_round,
                                    rid=rid, slot=slot,
                                    deadline=int(item.deadline_round),
                                    latency_rounds=latency)
            self._submit_wall.pop(rid, None)
        return (item.payload.rid, res)

    def step(self, max_rounds_on_device: int = 1
             ) -> list[tuple[int, SampleOut]]:
        """Resize check → policy decision → lockstep round(s) → drain.
        Returns finished requests as [(rid, SampleOut)].

        With ``overlap=True`` the same contract is served by the async
        double-buffered loop (:meth:`_step_overlap`): the decision for the
        next round is made from predicted lane state while the previous
        round is still in flight, and the done-flag readback happens only
        when the cost model says a lane is due to finish.

        Traced, each step is a ``serve/step`` phase holding ``serve/decide``
        (resize check, lane views, policy decision), ``dispatch/admit``,
        the round dispatch, ``verify/readback`` and ``serve/drain``.
        """
        with self.tracer.phase("serve/step", round_idx=self.round_count):
            if self.overlap:
                return self._step_overlap(max_rounds_on_device)
            return self._step_sync(max_rounds_on_device)

    def _step_sync(self, max_rounds_on_device: int = 1
                   ) -> list[tuple[int, SampleOut]]:
        dec = None
        with self.tracer.phase("serve/decide", round_idx=self.round_count):
            self._maybe_resize()
            free = [i for i, it in enumerate(self._slot_item) if it is None]
            if len(self.queue) and (free or self.policy.preemptive):
                view = EngineView(now=self.round_count, queue=self.queue,
                                  free_slots=free, lanes=self._lane_views(),
                                  cost=self.cost,
                                  lane_modes=self.lane_profile is not None)
                dec = self.policy.decide(view)
        if dec is not None:
            self._apply_decision(dec)
        if not self.has_inflight:
            # a fully idle grid is the lowest occupancy there is: idle
            # steps count toward the shrink hysteresis so a drained engine
            # still pages its slots out (each idle step ~ one round)
            if self.min_slots != self.max_slots and not len(self.queue):
                self._low_streak += 1
            self._last_dispatch_done = None  # gap timer: busy periods only
            return []

        live_ct = sum(it is not None for it in self._slot_item)
        r_dev = max(1, int(max_rounds_on_device))
        if r_dev > 1 and self._amortizable():
            self._mark_dispatch("multi", rounds=r_dev, live=live_ct)
            st, ran_dev = self._prog.multi(self.state,
                                           jnp.asarray(r_dev, jnp.int32))
            self._dispatch_done()
            self.state = st
            with self.tracer.phase("verify/readback",
                                   round_idx=self.round_count, live=live_ct):
                ran, done, rounds_used, chosen = jax.device_get(
                    (ran_dev, st.done, st.rounds_used, st.chosen))
            ran = int(ran)
        else:
            self._mark_dispatch("round", live=live_ct)
            self.state = self._prog.round(self.state)
            self._dispatch_done()
            with self.tracer.phase("verify/readback",
                                   round_idx=self.round_count, live=live_ct):
                done, rounds_used, chosen = jax.device_get(
                    (self.state.done, self.state.rounds_used,
                     self.state.chosen))
            ran = 1
        self._c_host_syncs.inc()
        self.round_count += ran
        self._c_live.inc(live_ct * ran)
        self._c_slot_rounds.inc(self.s * ran)
        self._c_wasted.inc((self.s - live_ct) * ran)

        out: list[tuple[int, SampleOut]] = []
        drain = [slot for slot in range(self.s)
                 if self._slot_item[slot] is not None and done[slot]]
        if drain:
            with self.tracer.phase("serve/drain", round_idx=self.round_count,
                                   lanes=len(drain)):
                out = self._drain_sync(drain, rounds_used, chosen)

        live_after = sum(it is not None for it in self._slot_item)
        self._update_streak(live_ct, live_after, ran)
        if not self.has_inflight:
            self._last_dispatch_done = None
        return out

    def _drain_sync(self, drain, rounds_used, chosen
                    ) -> list[tuple[int, SampleOut]]:
        """Gather, transfer and account the synchronous engine's finished
        lanes ``drain``, freeing their slots."""
        # one gather + one transfer for the whole drain set — a per-slot
        # device_get here was an extra host sync per finished request
        # (caught by the repro.analysis triage); the lane skip counters
        # ride the same transfer on a heterogeneous grid
        d_idx = np.asarray(drain)
        drain_skips = None
        if self.lane_profile is not None:
            results, drain_skips = jax.device_get(
                (self.state.result[d_idx], self.state.lanes.skips[d_idx]))
        else:
            results = jax.device_get(self.state.result[d_idx])
        out = []
        for j, slot in enumerate(drain):
            item = self._slot_item[slot]
            out.append(self._finish_lane(
                item, self._slot_iseq[slot], int(rounds_used[slot]),
                int(chosen[slot]), results[j], acc_round=self.round_count,
                slot=slot, admit_wall=self._admit_wall[slot],
                mode=self._slot_mode[slot],
                skips=int(drain_skips[j].sum())
                if drain_skips is not None else 0))
            self._slot_item[slot] = None  # slot is free; done flag stays
            self._pred_done[slot] = None  # until the next admission clears
            # it (the lane is frozen)
        return out

    # -- async double-buffered host loop --------------------------------------

    def _step_overlap(self, max_rounds_on_device: int = 1
                      ) -> list[tuple[int, SampleOut]]:
        """One async engine step: speculate → dispatch → verify → reconcile.

        The host classifies occupied lanes by the cost model's predicted
        accept round (``_pred_done``). While no lane is *due*, rounds are
        dispatched back-to-back with NO readback (the fast path — up to
        ``max_rounds_on_device`` rounds per program, capped so no predicted
        accept is overshot). When a lane is due, the host makes the next
        round's policy decision against the *predicted* post-drain state
        (due lanes presumed finished), applies it speculatively, dispatches
        the next round immediately, and only THEN blocks on the previous
        state's done flags:

        * prediction held → the dispatch already in flight is exactly the
          one the synchronous engine would have issued (confirmed — with
          exact ``rtol=0`` predictions this is every step, which is the
          bitwise-identity contract the tests pin);
        * prediction missed → the speculative admission targeted a lane
          that is still running: reinstate the retained pre-decision
          buffers (``admit`` is never donated), undo the host mirrors,
          re-decide against the true state, and re-dispatch — one discarded
          device round, counted in ``speculation_rollbacks`` /
          ``speculated_rounds_wasted``.

        Drained results are read from the RETAINED pre-round state (the
        non-donated ``round_keep`` program keeps it readable), and their
        latency/deadline accounting uses ``admit_round + rounds_used`` —
        identical numbers to the synchronous engine, independent of when
        the host discovered the accept.
        """
        with self.tracer.phase("serve/decide", round_idx=self.round_count):
            self._maybe_resize()
            now = self.round_count
            occupied = [i for i, it in enumerate(self._slot_item)
                        if it is not None]
            free = [i for i, it in enumerate(self._slot_item) if it is None]
            due = [s for s in occupied if self._pred_done[s] is None
                   or self._pred_done[s] <= now]
            idle = not occupied and not len(self.queue)
            want_decide = bool(len(self.queue)) and \
                bool(free or due or self.policy.preemptive)
            dec = Decision()
            if want_decide:
                view = EngineView(
                    now=now, queue=self.queue,
                    # predicted post-drain state: due lanes presumed
                    # finished. sorted() matches the ascending slot order
                    # the synchronous engine's free list has at the
                    # equivalent step
                    free_slots=sorted(free + due),
                    lanes=[ln for ln in self._lane_views()
                           if ln.slot not in due],
                    cost=self.cost, speculative=bool(due),
                    lane_modes=self.lane_profile is not None)
                dec = self.policy.decide(view)  # pops the admitted items
        if idle:
            if self.min_slots != self.max_slots:
                self._low_streak += 1
            self._last_dispatch_done = None
            return []

        if not due and not want_decide and occupied:
            # fast path: nothing can finish and nothing to decide — roll up
            # to r_dev rounds in one program, clipped so the next predicted
            # accept still lands on a step boundary; read NOTHING back
            r_dev = max(1, int(max_rounds_on_device))
            horizon = min(self._pred_done[s] - now for s in occupied)
            k = max(1, min(r_dev, horizon))
            self._mark_dispatch("roll" if k > 1 else "round", rounds=k,
                                live=len(occupied))
            if k == 1:
                self.state = self._prog.round(self.state)
            else:
                self.state = self._prog.roll(self.state,
                                             jnp.asarray(k, jnp.int32))
            self._dispatch_done()
            self.round_count += k
            live_ct = len(occupied)
            self._c_live.inc(live_ct * k)
            self._c_slot_rounds.inc(self.s * k)
            self._c_wasted.inc((self.s - live_ct) * k)
            self._update_streak(live_ct, live_ct, k)
            return []

        # -- event step: speculate + dispatch ahead of the verify ----------
        need_verify = bool(due)
        prev = self.state
        # drain metadata BEFORE applying the decision may overwrite it (a
        # confirmed speculative admit re-targets the due slot in the same
        # step)
        due_meta = {s: (self._slot_item[s], self._slot_iseq[s],
                        self._admit_round[s], self._admit_wall[s],
                        self._slot_mode[s])
                    for s in due}
        undo = None
        spec_admits = [a.slot for a in dec.admissions if a.slot in due_meta]
        if dec.admissions or dec.evictions:
            undo = self._apply_decision(dec, now=now,
                                        record_undo=need_verify)
            if spec_admits:
                self._c_spec.inc()
        # lanes presumed still running after the presumed drains: skip the
        # dispatch entirely when the grid would be empty (the synchronous
        # engine does not run a round on its final drain either)
        presumed_live = (len(occupied) - len(due)
                         + len(dec.admissions) - len(dec.evictions))
        dispatched = None
        if presumed_live > 0:
            self._mark_dispatch("round_keep" if need_verify else "round",
                                live=presumed_live)
            dispatched = (self._prog.round_keep(self.state) if need_verify
                          else self._prog.round(self.state))
            self._dispatch_done()
            self.round_count = now + 1

        out: list[tuple[int, SampleOut]] = []
        if need_verify:
            # ONE blocking readback per event step — the flags (and the due
            # results) of the round that finished while we were speculating
            due_idx = np.asarray(due, np.int32)
            with self.tracer.phase("verify/readback", round_idx=now,
                                   due=len(due)):
                if self.lane_profile is not None:
                    done, rounds_used, chosen, due_res, due_skips = \
                        jax.device_get(
                            (prev.done, prev.rounds_used, prev.chosen,
                             prev.result[due_idx], prev.lanes.skips[due_idx]))
                else:
                    done, rounds_used, chosen, due_res = jax.device_get(
                        (prev.done, prev.rounds_used, prev.chosen,
                         prev.result[due_idx]))
                    due_skips = None
            self._c_host_syncs.inc()
            failed = [s for s in spec_admits if not done[s]]
            if failed:
                # -- reconcile: a speculative admit targeted a live lane --
                self._c_spec_rollbacks.inc()
                self.tracer.instant("spec/rollback", round_idx=now,
                                    slots=list(failed),
                                    wasted=int(dispatched is not None))
                if dispatched is not None:
                    self._c_spec_wasted.inc()
                    self.round_count = now
                dispatched = None
                self.state = prev
                self._undo_decision(undo)
                out += self._drain_due(due, due_meta, done, rounds_used,
                                       chosen, due_res, due_skips)
                for s in due:
                    if not done[s] and self._slot_item[s] is not None:
                        self._pred_done[s] = now + 1  # re-verify next step
                free2 = [i for i, it in enumerate(self._slot_item)
                         if it is None]
                if len(self.queue) and (free2 or self.policy.preemptive):
                    with self.tracer.phase("serve/decide", round_idx=now):
                        view = EngineView(now=now, queue=self.queue,
                                          free_slots=free2,
                                          lanes=self._lane_views(),
                                          cost=self.cost,
                                          lane_modes=self.lane_profile
                                          is not None)
                        redo = self.policy.decide(view)
                    self._apply_decision(redo, now=now)
                if any(it is not None for it in self._slot_item):
                    self._mark_dispatch("round", live=sum(
                        it is not None for it in self._slot_item))
                    dispatched = self._prog.round(self.state)
                    self._dispatch_done()
                    self.round_count = now + 1
            else:
                if spec_admits:
                    self._c_spec_confirms.inc()
                    self.tracer.instant("spec/confirm", round_idx=now,
                                        slots=list(spec_admits))
                adm_slots = {a.slot for a in dec.admissions}
                out += self._drain_due(due, due_meta, done, rounds_used,
                                       chosen, due_res, due_skips)
                # lifecycle events of the now-confirmed speculative decision
                # — emitted after the due drains so the replaced residents'
                # spans close before the new residents' open
                self._trace_commit_undo(undo, now)
                for s in due:
                    if not done[s] and s not in adm_slots:
                        self._pred_done[s] = now + 1  # overdue: verify again
                # early accepts (actual < predicted) surface in the same
                # readback: schedule their drain for the next step
                for s, it in enumerate(self._slot_item):
                    if it is not None and s not in due_meta \
                            and s not in adm_slots and done[s]:
                        self._c_drain_lag.inc()
                        self._pred_done[s] = now + 1

        if dispatched is not None:
            self.state = dispatched
            live_ct = sum(it is not None for it in self._slot_item)
            self._c_live.inc(live_ct)
            self._c_slot_rounds.inc(self.s)
            self._c_wasted.inc(self.s - live_ct)
            self._update_streak(len(occupied), live_ct, 1)
        else:
            self._update_streak(
                len(occupied),
                sum(it is not None for it in self._slot_item), 0)
        if not self.has_inflight:
            self._last_dispatch_done = None
        return out

    def _drain_due(self, due, due_meta, done, rounds_used, chosen, due_res,
                   due_skips=None) -> list[tuple[int, SampleOut]]:
        """Drain the due lanes whose accept actually fired, from the
        retained pre-round arrays. A slot whose speculative re-admission was
        confirmed already carries its NEW item in the mirrors — the old
        lane's identity (and lane mode) comes from ``due_meta`` and the
        slot is not freed."""
        with self.tracer.phase("serve/drain", round_idx=self.round_count,
                               lanes=len(due)):
            out = []
            for j, s in enumerate(due):
                item, i_seq, admit_round, admit_wall, mode = due_meta[s]
                if not done[s]:
                    continue
                ru = int(rounds_used[s])
                out.append(self._finish_lane(item, i_seq, ru, int(chosen[s]),
                                             due_res[j],
                                             acc_round=admit_round + ru,
                                             slot=s, admit_wall=admit_wall,
                                             mode=mode,
                                             skips=int(due_skips[j].sum())
                                             if due_skips is not None else 0))
                if self._slot_item[s] is item:
                    self._slot_item[s] = None  # freed; stale flags stay until
                    self._pred_done[s] = None  # the next admission (frozen lane)
            return out

    def run_until_drained(self, max_rounds: Optional[int] = None,
                          max_rounds_on_device: int = 1
                          ) -> list[tuple[int, SampleOut]]:
        """Step until queue and grid are empty; returns all (rid, SampleOut)."""
        budget = max_rounds if max_rounds is not None else \
            2 * (len(self.queue) + self.max_slots) * (self.n + 1)  # 2x: preempt
        limit = self.round_count + budget  # relative: engines are long-lived
        served: list[tuple[int, SampleOut]] = []
        while len(self.queue) or self.has_inflight:
            served += self.step(max_rounds_on_device=max_rounds_on_device)
            # a multi-round step can legally overshoot `limit` by up to
            # max_rounds_on_device-1 rounds while finishing the last lane —
            # only raise when the budget is spent AND work remains
            if self.round_count >= limit \
                    and (len(self.queue) or self.has_inflight):
                raise RuntimeError(
                    f"engine did not drain within {budget} rounds")
        return served

    def stats(self) -> dict:
        """Throughput + latency percentiles, all in lockstep-round units.

        Every value is rendered FROM the metrics registry (plus the handful
        of structural attributes like the bucket ladder) — the dict is a
        view, not a second set of books. Latency/speedup percentiles come
        from bounded reservoirs: exact up to the reservoir capacity
        (default 2048 served requests), an unbiased uniform-sample estimate
        beyond; count/mean stay exact forever (see obs/metrics.py).
        """
        served = int(self._c_served.value)
        rounds = max(1, self.round_count)
        deadline_total = int(self._c_deadline_total.value)
        misses = int(self._c_deadline_misses.value)
        # freshen the gauges so a registry snapshot taken after stats()
        # carries the same numbers the dict shows
        self.metrics.gauge("serve.rounds_total").set(float(self.round_count))
        self.metrics.gauge("serve.queue_depth").set(float(len(self.queue)))
        return {
            "served": served,
            "rounds_total": self.round_count,
            "throughput_req_per_round": served / rounds,
            "occupancy": (self._c_live.value
                          / max(1, self._c_slot_rounds.value)),
            "latency_rounds_p50": self._h_latency.percentile(50),
            "latency_rounds_p95": self._h_latency.percentile(95),
            "mean_speedup": self._h_speedup.mean,
            "policy": self.policy.name,
            "host_syncs": int(self._c_host_syncs.value),
            # async-overlap accounting (all zero for overlap=False)
            "overlap": self.overlap,
            "speculations": int(self._c_spec.value),
            "speculation_confirms": int(self._c_spec_confirms.value),
            "speculation_rollbacks": int(self._c_spec_rollbacks.value),
            "speculated_rounds_wasted": int(self._c_spec_wasted.value),
            "drain_lag_rounds": int(self._c_drain_lag.value),
            # round-gap timer: host-side monotonic gap between consecutive
            # device dispatches over a busy grid (~0 == device never starved)
            "dispatches": int(self._c_dispatches.value),
            "round_gap_count": self._h_gap.count,
            "round_gap_mean_s": self._h_gap.mean,
            "round_gap_p95_s": self._h_gap.percentile(95),
            "round_gap_max_s": self._h_gap.max if self._h_gap.count else 0.0,
            "deadline_total": deadline_total,
            "deadline_misses": misses,
            "deadline_miss_rate": (misses / deadline_total
                                   if deadline_total else 0.0),
            "preemptions": int(self._c_preempt.value),
            "preempted_rounds_wasted": int(self._c_preempt_wasted.value),
            # elastic-capacity accounting
            "num_slots": self.s,
            "min_slots": self.min_slots,
            "max_slots": self.max_slots,
            "wasted_slot_rounds": int(self._c_wasted.value),
            "resizes": int(self._c_resizes.value),
            "grows": int(self._c_grows.value),
            "shrinks": int(self._c_shrinks.value),
            "resize_vetoes": int(self._c_vetoes.value),
            "migrations": int(self._c_migrations.value),
            "buckets_visited": sorted(self._buckets_visited),
            "retraces": self.executor.retraces,
            "migration_traces": self.executor.migration_traces,
            # heterogeneous-lane accounting (all zero / disabled on a
            # homogeneous grid — lane_profile=None)
            "lane_modes_enabled": self.lane_profile is not None,
            "lane_profile": [sp.role + ("+skip" if sp.skip else "")
                             for sp in (self.lane_profile or ())],
            "lane_skips": int(self._c_lane_skips.value),
            "lane_served_nonexact": int(self._c_lane_nonexact.value),
            "lane_promotes": int(self._c_lane_promotes.value),
            "lane_skip_rate": {m: self.cost.skip_rate(m)
                               for m in ("adaptive", "draft")},
            # which solver-step implementation served this engine's rounds
            # (fused-accept-pallas | fused-accept-oracle | jnp-unfused)
            "kernel_path": self.executor.kernel_path,
            # observed accept rounds (EMA per (i_seq, rtol) — feeds the cost
            # model's calibrated predictions; see sched/README.md)
            "accept_rounds_observed": self.cost.accept_table_json(),
        }

    def write_trace(self, path: str, meta: Optional[dict] = None) -> dict:
        """Export this engine's trace + metrics snapshot as one Chrome
        trace-event JSON artifact (open it in ui.perfetto.dev; verify it
        with ``python -m repro.obs check``)."""
        from repro.obs import write_chrome_trace
        self.stats()  # refresh the snapshot gauges
        info = {"engine": "continuous", "policy": self.policy.name,
                "overlap": self.overlap, "n_steps": self.n, "k": self.k,
                "lane_modes": self.lane_profile is not None}
        if meta:
            info.update(meta)
        return write_chrome_trace(path, self.tracer, metrics=self.metrics,
                                  meta=info)
