"""Unified round-executor layer: one compile path for every serve engine.

Before this module, the slot-round / admission / multi-round / streaming
programs were compiled in three private places (``StreamingSampler._run``,
``ChordsEngine`` via its sampler, and ``ContinuousEngine._round_fn`` /
``_admit_fn`` / ``_multi_round_fn``), each hard-coding one grid shape. The
:class:`RoundExecutor` owns all of them now:

* a :class:`GridSpec` names a slot grid — (S, K, latent shape, dtype,
  sharding tag, device-rounds hint) — and is the *key* of a bounded LRU
  trace cache: the first time a spec is requested its program set (round,
  admit, multi-round, fresh state) is built from
  ``core.chords.make_slot_round_body`` and jitted (**one retrace, counted**);
  every later request for the same spec is a cache hit, including re-entry
  after other specs were used in between (no thrash retraces — the elastic
  engine relies on this when it bounces between capacity buckets);
* a :class:`StreamSpec` keys the batch streaming-accept program
  (``StreamingSampler``'s early-exit ``while_loop``) the same way;
* ``migrate(src_spec, dst_spec)`` returns the lane-migration program — the
  masked-gather :func:`repro.core.chords.gather_slots` over a full
  :class:`SlotState` — that moves live lanes between grids of different S
  during an elastic resize, copying every migrated lane's carry bit-exactly.

``use_kernel=True`` builds every slot-round body on the fused Pallas
solver-step + rectification + accept-reduction kernel
(``repro.kernels.rectify``) instead of composed jnp ops: the rtol accept
sums are reduced inside the kernel pass (no full-latent error array in the
round jaxpr) and ``accept_from_sums`` finishes the decision on [S, K]
scalars. On CPU the kernel runs as its jnp oracle and outputs are bitwise
identical either way (parity test in ``tests/test_executor.py``); on a TPU
backend the real Pallas lowering runs. ``kernel_path`` in ``stats()``
names which implementation served.

Weights are program arguments: a :class:`repro.core.ode.ParamDrift`'s
``params`` are passed to every program that evaluates the drift (round,
roll, multi, stream) through :class:`BoundProgram`, so one device copy of
the weights serves all programs and none of them embeds the weights as
constants. Plain ``(x, t)`` drifts bind the empty pytree ``()``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import scheduler
from repro.core.chords import (ChordsCarry, LaneSpec, LaneState,
                               accept_from_sums, accept_test, bmask,
                               chords_init_carry, gather_slots,
                               lane_init_state, make_round_body,
                               make_slot_round_body, reset_lanes,
                               reset_slots, slot_init_carry)
from repro.core.ode import split_drift
from repro.kernels import resolve_kernel_mode
from repro.obs import NULL_TRACER, MetricsRegistry


def _scoped(name: str, fn: Callable) -> Callable:
    """Wrap a program body in a ``jax.named_scope`` so profiler captures
    (and compiled HLO metadata) attribute device time to the serve program
    it belongs to. Trace-time only — it adds **no** jaxpr equations, so the
    static-analysis passes over these bodies see identical programs."""
    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    wrapped.__name__ = getattr(fn, "__name__", name)
    return wrapped


def ambient_sharding_tag() -> Optional[str]:
    """Stable tag for the active ``use_sharding`` context (``None`` outside
    one). Engines put it in their spec keys so programs traced under
    different mesh contexts never alias a cache entry."""
    from repro.dist.sharding import current_ctx
    ctx = current_ctx()
    if ctx is None:
        return None
    mesh = ctx.mesh
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return f"mesh={sorted(axes.items())};rules={sorted(ctx.rules.items())}"


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Hashable name of one slot grid — the trace-cache key.

    ``sharding`` is an opaque tag for the ambient mesh context (programs
    compiled under different ``use_sharding`` contexts must not share cache
    entries); ``device_rounds`` is an optional static CAP on the multi-round
    device loop — the compiled ``multi`` program never runs more than this
    many rounds per host sync regardless of the traced budget it is called
    with. ``None`` (the default, and what the engines pass) leaves the
    budget fully traced so varying R never retraces.

    ``donate=True`` donates the incoming ``SlotState`` buffers to the
    state-advancing programs (``round`` / ``roll`` / ``multi``), so the
    double-buffered async engine never holds two copies of the grid in
    device memory. ``admit`` and ``round_keep`` are never donated: ``admit``
    is the rollback anchor and ``round_keep`` exists precisely so the async
    engine can keep the pre-round state readable while the next round is in
    flight.

    ``lane_profile`` (a tuple of :class:`repro.core.chords.LaneSpec`, or
    ``None``) selects the heterogeneous round body: the grid's
    :class:`SlotState` gains a ``LaneState`` and the admit program two
    per-slot gate operands (``draft_on``/``skip_tau``). ``None`` builds
    exactly the homogeneous programs — the profile is part of the cache key,
    so homogeneous and heterogeneous grids of the same shape never alias.
    """

    num_slots: int
    num_cores: int
    latent_shape: Tuple[int, ...]
    dtype: str = "float32"
    sharding: Optional[str] = None
    device_rounds: Optional[int] = None
    donate: bool = False
    lane_profile: Optional[Tuple[LaneSpec, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "latent_shape", tuple(self.latent_shape))
        if self.lane_profile is not None:
            object.__setattr__(self, "lane_profile",
                               tuple(self.lane_profile))
        if self.num_slots < 1 or self.num_cores < 1:
            raise ValueError(f"need S >= 1 and K >= 1, got {self}")


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """Trace-cache key for the batch streaming-accept program."""

    num_cores: int
    i_seq: Tuple[int, ...]
    rtol: float
    batched: bool = False
    sharding: Optional[str] = None

    def __post_init__(self):
        object.__setattr__(self, "i_seq", tuple(int(i) for i in self.i_seq))


class SlotState(NamedTuple):
    """Device-side state of the continuous-batching slot grid (a pytree).

    Every leaf leads with the slot axis — which is what lets
    ``gather_slots`` migrate whole lanes between grids as pure row copies.
    """

    carry: ChordsCarry     # [S, K, ...] lockstep grid
    i_arr: jax.Array       # [S, K] per-slot init sequence
    rtol: jax.Array        # [S] per-slot accept tolerance
    rounds: jax.Array      # [S] next lockstep round for each slot (1-based)
    live: jax.Array        # [S] slot occupied and still iterating
    done: jax.Array        # [S] converged, result buffered for drain
    has_last: jax.Array    # [S] a previous streamed output exists
    last_out: jax.Array    # [S, ...] latest streamed output per slot
    result: jax.Array      # [S, ...] accepted output (valid where done)
    rounds_used: jax.Array  # [S] lockstep rounds at accept
    chosen: jax.Array      # [S] accepted core index
    # LaneState on heterogeneous grids; () on homogeneous ones — the empty
    # tuple has zero pytree leaves, so homogeneous programs (and their
    # jaxprs) are untouched by the field existing
    lanes: object = ()


class BoundProgram(NamedTuple):
    """A jitted ``program(params, *args)`` with its weights bound: calling
    it (or lowering it) passes ``params`` as a device argument."""

    jitted: Callable
    params: object

    def __call__(self, *args):
        return self.jitted(self.params, *args)

    def lower(self, *args):
        return self.jitted.lower(self.params, *args)


class GridPrograms(NamedTuple):
    """One GridSpec's compiled program set (all jitted, shared via cache)."""

    spec: GridSpec
    round: Callable      # (SlotState) -> SlotState  (donated iff spec.donate)
    round_keep: Callable  # same program, input NEVER donated (async verify)
    roll: Callable       # (SlotState, k) -> SlotState: k rounds, no accept exit
    multi: Callable      # (SlotState, max_rounds) -> (SlotState, ran)
    admit: Callable      # (SlotState, mask, keys, i_arr, rtol) -> SlotState
    init_state: Callable  # () -> SlotState (host-side, not compiled)


class ProgramRecord(NamedTuple):
    """One enumerable program: the UNJITTED body + abstract example args.

    The static-analysis subsystem (``repro.analysis``) consumes these —
    ``jax.make_jaxpr(fn)(*args)`` traces the exact program the executor
    would compile, without compiling or allocating anything.
    """

    name: str     # e.g. "grid[S=4,K=4,(4,),f32]/round"
    kind: str     # round | admit | multi | roll | stream | migrate
    fn: Callable
    args: Tuple   # ShapeDtypeStruct pytrees matching the program signature


def _slot_state_structs(spec: GridSpec) -> SlotState:
    """Abstract ``SlotState`` for ``spec`` (ShapeDtypeStructs, no device
    memory) — mirrors ``init_state`` leaf for leaf."""
    s, k = spec.num_slots, spec.num_cores
    dtype = jnp.dtype(spec.dtype)
    lat = jax.ShapeDtypeStruct((s,) + spec.latent_shape, dtype)
    grid_lat = jax.ShapeDtypeStruct((s, k) + spec.latent_shape, dtype)
    sk_i32 = jax.ShapeDtypeStruct((s, k), jnp.int32)
    s_i32 = jax.ShapeDtypeStruct((s,), jnp.int32)
    s_bool = jax.ShapeDtypeStruct((s,), jnp.bool_)
    sk_f32 = jax.ShapeDtypeStruct((s, k), jnp.float32)
    lanes: object = ()
    if spec.lane_profile is not None:
        lanes = LaneState(
            pos=sk_i32, f_norm=sk_f32, stab=sk_f32, skips=sk_i32,
            draft_on=s_bool,
            skip_tau=jax.ShapeDtypeStruct((s,), jnp.float32))
    return SlotState(
        carry=ChordsCarry(x=grid_lat, x_snap=grid_lat, f_snap=grid_lat,
                          p=sk_i32, finals=grid_lat),
        i_arr=sk_i32,
        rtol=jax.ShapeDtypeStruct((s,), jnp.float32),
        rounds=s_i32, live=s_bool, done=s_bool, has_last=s_bool,
        last_out=lat, result=lat,
        rounds_used=s_i32, chosen=s_i32, lanes=lanes,
    )


def _grid_fns(drift, tgrid, n: int, spec: GridSpec,
              use_kernel: bool) -> dict:
    """The slot-grid program bodies for one GridSpec, UNJITTED.

    ``_build_grid`` wraps these in ``jax.jit`` for serving;
    ``RoundExecutor.enumerate_programs`` hands them (plus abstract args) to
    the static-analysis passes, which need raw traceable callables. The
    drift-evaluating bodies (round, multi, roll) take the drift's weights
    as their first argument (see :func:`repro.core.ode.split_drift`).
    """
    s, k = spec.num_slots, spec.num_cores
    dtype = jnp.dtype(spec.dtype)
    # use_kernel engages the FUSED round: solver step + rectification +
    # accept reduction in one kernel pass (err/out sums leave the kernel as
    # [S, K] scalars — accept_from_sums finishes on those, so the jaxpr has
    # no full-latent error array between the step and the accept decision).
    # use_kernel=False keeps the composed-jnp round with accept_test on the
    # materialized output; both paths are bitwise identical on CPU.
    fuse_accept = bool(use_kernel)
    hetero = spec.lane_profile is not None
    apply, _ = split_drift(drift)

    @functools.partial(_scoped, "chords.step")
    def round_fn(params, st: SlotState) -> SlotState:
        """One lockstep round for every live slot + per-slot accept test.
        The round runs under the named scope ``chords.step`` and its drift
        under ``drift`` (``repro.obs.scopes``)."""
        slot_round = make_slot_round_body(
            _scoped("drift", functools.partial(apply, params)), tgrid, n, k,
            use_kernel=use_kernel, fuse_accept=fuse_accept,
            lane_profile=spec.lane_profile)
        active = st.live
        # slot_round's emitted IS (emit_rounds == r) & active — the live
        # cores that wrote t=1 this round; recomputing it from the
        # scheduler table here left the returned mask dead in the jaxpr
        # (caught by repro.analysis jaxpr:dead-code)
        lanes = st.lanes
        if hetero and fuse_accept:
            carry, lanes, hit, err_sq, out_sq = slot_round(
                st.carry, st.lanes, st.i_arr, st.rounds, active, st.last_out)
        elif hetero:
            carry, lanes, hit = slot_round(st.carry, st.lanes, st.i_arr,
                                           st.rounds, active)
        elif fuse_accept:
            carry, hit, err_sq, out_sq = slot_round(
                st.carry, st.i_arr, st.rounds, active, st.last_out)
        else:
            carry, hit = slot_round(st.carry, st.i_arr, st.rounds, active)
        emit = scheduler.emit_rounds_jnp(st.i_arr, n)  # [S, K]
        r = st.rounds
        any_emit = jnp.any(hit, axis=1)
        ek = jnp.argmax(hit, axis=1).astype(jnp.int32)  # slowest emitter wins
        out = carry.x[jnp.arange(s), ek]  # [S, ...]

        if fuse_accept:
            # the emitting core's carry.x row IS x_new (alive & live there),
            # so its in-kernel sums are the accept_test sums of `out` —
            # dead-lane garbage in err_sq/out_sq is gated off by the masks
            sek = (jnp.arange(s), ek)
            agree = accept_from_sums(err_sq[sek], out_sq[sek], st.rtol)
        else:
            agree = accept_test(out, st.last_out, st.rtol, 1)
        ok = any_emit & st.has_last & agree
        # core 0's emission is the exact sequential solve: force-accept it so
        # no request outlives its own N rounds
        final = any_emit & (r >= emit[:, 0])
        acc = (ok | final) & active
        result = jnp.where(bmask(acc, out), out, st.result)
        return SlotState(
            carry=carry,
            i_arr=st.i_arr,
            rtol=st.rtol,
            rounds=jnp.where(active, r + 1, r),
            live=st.live & ~acc,
            done=st.done | acc,
            has_last=st.has_last | any_emit,
            last_out=jnp.where(bmask(any_emit, out), out, st.last_out),
            result=result,
            rounds_used=jnp.where(acc, r, st.rounds_used),
            chosen=jnp.where(acc, ek, st.chosen),
            lanes=lanes,
        )

    def _admit_common(st: SlotState, mask, keys, i_arr, rtol) -> SlotState:
        """Masked admission: reset lanes + per-slot accept state in place.

        ``keys`` is ``uint32[S, 2]`` — one PRNG key row per slot (unadmitted
        rows are ignored through the mask). The init noise is generated
        *inside* the program: the host never materializes x0, so an
        admission batch costs zero device<->host latent transfers. The
        vmapped ``random.normal`` is bitwise identical to per-key unbatched
        draws (the same equivalence ``ChordsEngine`` already relies on).
        """
        x0 = jax.vmap(lambda kk: jax.random.normal(
            kk, spec.latent_shape))(keys).astype(dtype)
        carry = reset_slots(st.carry, mask, x0, i_arr)
        m_lat = bmask(mask, st.last_out)
        return SlotState(
            carry=carry,
            i_arr=jnp.where(mask[:, None], i_arr, st.i_arr),
            rtol=jnp.where(mask, rtol, st.rtol),
            rounds=jnp.where(mask, 1, st.rounds),
            live=st.live | mask,
            done=st.done & ~mask,
            has_last=st.has_last & ~mask,
            last_out=jnp.where(m_lat, 0.0, st.last_out),
            result=jnp.where(m_lat, 0.0, st.result),
            rounds_used=jnp.where(mask, 0, st.rounds_used),
            chosen=jnp.where(mask, 0, st.chosen),
            lanes=st.lanes,
        )

    if hetero:
        def admit_fn(st: SlotState, mask, keys, i_arr, rtol,
                     draft_on, skip_tau) -> SlotState:
            """Heterogeneous admission: ``_admit_common`` plus the admitted
            request's lane gates (``draft_on``: [S] bool opting into draft
            smoothing, ``skip_tau``: [S] f32 skip threshold, 0 = exact)."""
            base = _admit_common(st, mask, keys, i_arr, rtol)
            return base._replace(
                lanes=reset_lanes(st.lanes, mask, draft_on, skip_tau))
    else:
        admit_fn = _admit_common

    def multi_fn(params, st: SlotState, max_rounds):
        """Up to ``max_rounds`` lockstep rounds in ONE device program.

        The ``lax.while_loop`` exits as soon as any slot's accept fires
        (``done`` rises relative to the flags at entry — drained slots keep
        their stale flag until re-admission, so the delta is exactly "newly
        finished") or the round budget elapses. The host only reads back
        afterwards: one sync amortized over up to R rounds. ``max_rounds``
        is a traced scalar, so varying R never retraces;
        ``spec.device_rounds`` (when set) is a static per-grid cap on it.

        The entry flags are captured *inside* the program (not passed as an
        argument) so donating the state never aliases a still-needed input.
        """
        done0 = st.done
        if spec.device_rounds is not None:
            max_rounds = jnp.minimum(max_rounds, spec.device_rounds)

        def cond(c):
            st_, i = c
            return (i < max_rounds) & jnp.any(st_.live) \
                & ~jnp.any(st_.done & ~done0)

        def body(c):
            st_, i = c
            return round_fn(params, st_), i + 1

        return jax.lax.while_loop(cond, body,
                                  (st, jnp.asarray(0, jnp.int32)))

    def roll_fn(params, st: SlotState, k):
        """Exactly ``k`` lockstep rounds with NO accept-driven exit.

        The async engine's fast path: when the cost model says no lane can
        finish for the next ``k`` rounds, the host dispatches them all in
        one program and reads nothing back. Rounds on an all-dead grid are
        the identity (the live-mask freezes every lane), so the early
        all-dead exit below is a pure optimization — the result is bitwise
        the k-fold composition of ``round``.
        """
        def cond(c):
            st_, i = c
            return (i < k) & jnp.any(st_.live)

        def body(c):
            st_, i = c
            return round_fn(params, st_), i + 1

        st_out, _ = jax.lax.while_loop(cond, body,
                                       (st, jnp.asarray(0, jnp.int32)))
        return st_out

    def init_state() -> SlotState:
        lat = jnp.zeros((s,) + spec.latent_shape, dtype)
        return SlotState(
            carry=slot_init_carry(s, k, spec.latent_shape, dtype),
            i_arr=jnp.zeros((s, k), jnp.int32),
            rtol=jnp.zeros((s,), jnp.float32),
            rounds=jnp.ones((s,), jnp.int32),
            live=jnp.zeros((s,), bool),
            done=jnp.zeros((s,), bool),
            has_last=jnp.zeros((s,), bool),
            last_out=lat, result=lat,
            rounds_used=jnp.zeros((s,), jnp.int32),
            chosen=jnp.zeros((s,), jnp.int32),
            lanes=lane_init_state(s, k) if hetero else (),
        )

    tag = f"serve.grid_s{s}k{k}"
    return {"round": _scoped(f"{tag}.round", round_fn),
            "admit": _scoped(f"{tag}.admit", admit_fn),
            "multi": _scoped(f"{tag}.multi", multi_fn),
            "roll": _scoped(f"{tag}.roll", roll_fn),
            "init_state": init_state}


def _build_grid(drift, tgrid, n: int, spec: GridSpec,
                use_kernel: bool) -> GridPrograms:
    """Build + jit the slot-grid program set for one GridSpec.

    The drift-evaluating programs are :class:`BoundProgram` instances
    carrying the drift's weights. When ``spec.donate`` the state-advancing
    programs donate their input ``SlotState`` (never the weights), so
    stepping the grid reuses the old buffers instead of holding both
    generations live. ``round_keep`` is the same
    round program compiled WITHOUT donation — the async engine dispatches
    through it when it must keep the pre-round state readable for the
    verify/rollback readback (when not donating it is simply ``round``).
    ``admit`` is never donated: the engine may need to re-admit against the
    retained pre-decision state after a speculation rollback.
    """
    fns = _grid_fns(drift, tgrid, n, spec, use_kernel)
    _, params = split_drift(drift)
    don = (1,) if spec.donate else ()
    bind = lambda name, d: BoundProgram(
        jax.jit(fns[name], donate_argnums=d), params)
    round_prog = bind("round", don)
    return GridPrograms(spec=spec, round=round_prog,
                        round_keep=(bind("round", ()) if spec.donate
                                    else round_prog),
                        roll=bind("roll", don),
                        multi=bind("multi", don),
                        admit=jax.jit(fns["admit"]),
                        init_state=fns["init_state"])


def _build_stream_fn(drift, tgrid, n: int, spec: StreamSpec,
                     use_kernel: bool) -> Callable:
    """The early-exit streaming program body (StreamingSampler's), UNJITTED
    (``_build_stream`` jits it; ``enumerate_programs`` lints it raw):
    ``run(params, x0, live)`` with the drift's weights first."""
    i_arr = jnp.asarray(spec.i_seq, jnp.int32)
    emit = jnp.asarray(scheduler.emit_rounds(list(spec.i_seq), n))
    apply, _ = split_drift(drift)
    rtol, batched = spec.rtol, spec.batched
    bdim = 1 if batched else 0

    def run(params, x0, live):
        round_body = make_round_body(functools.partial(apply, params),
                                     tgrid, i_arr, n, spec.num_cores,
                                     use_kernel=use_kernel)

        def cond(state):
            _, r, accepted = state[0], state[1], state[2]
            return (~jnp.all(accepted)) & (r <= n)

        def body(state):
            (carry, r, accepted, last_out, has_last, chosen, rounds,
             result) = state
            carry, _ = round_body(carry, r)
            emitted_k = jnp.argmax(emit == r)  # core emitting this round
            any_emit = jnp.any(emit == r)
            out = carry.x[emitted_k]
            ok = any_emit & has_last & accept_test(out, last_out, rtol, bdim) \
                & (~accepted)
            result = jnp.where(bmask(ok, out), out, result)
            rounds = jnp.where(ok, r, rounds)
            chosen = jnp.where(ok, emitted_k, chosen)
            accepted = accepted | ok
            last_out = jnp.where(any_emit, out, last_out)
            has_last = has_last | any_emit
            return (carry, r + 1, accepted, last_out, has_last, chosen,
                    rounds, result)

        carry = chords_init_carry(x0, i_arr, spec.num_cores)
        state = (carry, jnp.asarray(1),
                 ~live, jnp.zeros_like(x0),
                 jnp.asarray(False), jnp.zeros(live.shape, jnp.int32),
                 jnp.zeros(live.shape, jnp.int32), jnp.zeros_like(x0))
        (carry, r, accepted, last_out, _, chosen, rounds,
         result) = jax.lax.while_loop(cond, body, state)
        # requests that never early-exited take the final emission —
        # core 0's full-round output, i.e. the sequential solve
        fell_through = live & (rounds == 0)
        result = jnp.where(bmask(fell_through, result), last_out, result)
        rounds = jnp.where(fell_through, n, rounds)
        return result, rounds, chosen

    return run


def _build_stream(drift, tgrid, n: int, spec: StreamSpec,
                  use_kernel: bool) -> BoundProgram:
    """Build + jit the early-exit streaming program (StreamingSampler's)."""
    return BoundProgram(
        jax.jit(_scoped(f"serve.stream_k{spec.num_cores}",
                        _build_stream_fn(drift, tgrid, n, spec, use_kernel))),
        split_drift(drift)[1])


class RoundExecutor:
    """Owner of every compiled serve program, behind a keyed LRU trace cache.

    One executor wraps one ``(drift, tgrid)`` pair; engines either build
    their own or share one (sharing is what makes the trace-count
    accounting meaningful across engines). ``retraces`` counts grid-spec
    cache misses — the acceptance contract is *one per distinct GridSpec
    ever touched*, cache hits thereafter (bucket re-entry is free);
    ``stream_traces`` and ``migration_traces`` count the other two program
    families the same way.
    """

    def __init__(self, drift: Callable, tgrid, n_steps: Optional[int] = None,
                 use_kernel: bool = False,
                 max_entries: int = 8, tracer=None, metrics=None):
        self.drift = drift
        self.tgrid = tgrid
        self.n = int(n_steps) if n_steps is not None \
            else int(tgrid.shape[0]) - 1
        if self.n != int(tgrid.shape[0]) - 1:
            raise ValueError(
                f"n_steps {self.n} != len(tgrid)-1 {int(tgrid.shape[0]) - 1}")
        # the fused kernel's dispatch (Pallas on TPU, jnp oracle on CPU) is
        # resolved from the backend by repro.kernels.resolve_kernel_mode
        self.use_kernel = use_kernel
        self.max_entries = max(1, int(max_entries))
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._grids: "collections.OrderedDict[GridSpec, GridPrograms]" = \
            collections.OrderedDict()
        self._streams: "collections.OrderedDict[StreamSpec, Callable]" = \
            collections.OrderedDict()
        # one jitted gather serves every migration pair — jax's own cache
        # keys it by shapes, so (S_src, S_dst) pairs each trace once
        self._migrate = jax.jit(_scoped("serve.migrate", gather_slots))
        self._c_retraces = self.metrics.counter("executor.retraces")
        self._c_stream_traces = self.metrics.counter(
            "executor.stream_traces")

    # -- caches ---------------------------------------------------------------

    @staticmethod
    def _lru_get(cache, key, build, max_entries):
        hit = cache.get(key)
        if hit is not None:
            cache.move_to_end(key)
            return hit, False
        val = build()
        cache[key] = val
        while len(cache) > max_entries:
            cache.popitem(last=False)
        return val, True

    def reserve_grid_capacity(self, n: int) -> None:
        """Ensure the grid cache can take ``n`` more specs without evicting
        resident ones. Engines call this with their bucket-ladder size, so
        ladder re-entry can never evict-and-retrace — even when several
        engines share one executor."""
        self.max_entries = max(self.max_entries, len(self._grids) + int(n))

    def grid(self, spec: GridSpec) -> GridPrograms:
        """Program set for ``spec`` — compiled once, cache-hit thereafter."""
        progs, missed = self._lru_get(
            self._grids, spec,
            lambda: _build_grid(self.drift, self.tgrid, self.n, spec,
                                self.use_kernel),
            self.max_entries)
        if missed:
            self._c_retraces.inc()
            self.tracer.instant("retrace", kind="grid",
                                spec=f"S={spec.num_slots},"
                                     f"K={spec.num_cores}")
        return progs

    def stream(self, spec: StreamSpec) -> BoundProgram:
        """Jitted ``(x0, live) -> (result, rounds, chosen)`` early-exit
        streaming program for ``spec``."""
        fn, missed = self._lru_get(
            self._streams, spec,
            lambda: _build_stream(self.drift, self.tgrid, self.n, spec,
                                  self.use_kernel),
            self.max_entries)
        if missed:
            self._c_stream_traces.inc()
            self.tracer.instant("retrace", kind="stream",
                                spec=f"K={spec.num_cores},"
                                     f"batched={spec.batched}")
        return fn

    def migrate(self, src_spec: GridSpec, dst_spec: GridSpec) -> Callable:
        """Jitted lane-migration program ``(dst_state, src_state, mask,
        src_idx) -> SlotState`` between two grids (masked row gather — every
        migrated lane's carry is copied bit-exactly)."""
        if src_spec.num_cores != dst_spec.num_cores \
                or src_spec.latent_shape != dst_spec.latent_shape \
                or src_spec.dtype != dst_spec.dtype \
                or src_spec.lane_profile != dst_spec.lane_profile:
            raise ValueError(
                f"can only migrate lanes between grids differing in S: "
                f"{src_spec} -> {dst_spec}")
        return self._migrate

    # -- static-analysis enumeration hook -------------------------------------

    def enumerate_programs(self, grid_specs=(), stream_specs=(),
                           stream_latent_shape=(4,), stream_batch: int = 2,
                           migrate_pairs=()) -> list:
        """Every program this executor can build for the given specs, as
        :class:`ProgramRecord`s with UNJITTED bodies + abstract args.

        This is the enumeration surface ``repro.analysis`` lints: jaxpr
        passes ``jax.make_jaxpr(rec.fn)(*rec.args)`` each record without
        compiling, allocating, or touching the trace cache (records are
        built fresh — enumeration never pollutes ``retraces``).
        """
        records: list = []
        params = split_drift(self.drift)[1]
        bind = lambda fn: functools.partial(fn, params)
        for spec in grid_specs:
            fns = _grid_fns(self.drift, self.tgrid, self.n, spec,
                            self.use_kernel)
            st = _slot_state_structs(spec)
            s, k = spec.num_slots, spec.num_cores
            lane_tag = ""
            admit_extra: tuple = ()
            if spec.lane_profile is not None:
                roles = "".join("D" if sp.role == "draft" else
                                ("A" if sp.skip else "R")
                                for sp in spec.lane_profile)
                lane_tag = f",lanes={roles}"
                admit_extra = (jax.ShapeDtypeStruct((s,), jnp.bool_),
                               jax.ShapeDtypeStruct((s,), jnp.float32))
            tag = (f"grid[S={s},K={k},{spec.latent_shape},"
                   f"{jnp.dtype(spec.dtype).name}{lane_tag}]")
            records.append(ProgramRecord(
                f"{tag}/round", "round", bind(fns["round"]), (st,)))
            records.append(ProgramRecord(
                f"{tag}/admit", "admit", fns["admit"],
                (st, jax.ShapeDtypeStruct((s,), jnp.bool_),
                 jax.ShapeDtypeStruct((s, 2), jnp.uint32),
                 jax.ShapeDtypeStruct((s, k), jnp.int32),
                 jax.ShapeDtypeStruct((s,), jnp.float32)) + admit_extra))
            records.append(ProgramRecord(
                f"{tag}/multi", "multi", bind(fns["multi"]),
                (st, jax.ShapeDtypeStruct((), jnp.int32))))
            records.append(ProgramRecord(
                f"{tag}/roll", "roll", bind(fns["roll"]),
                (st, jax.ShapeDtypeStruct((), jnp.int32))))
        for spec in stream_specs:
            fn = bind(_build_stream_fn(self.drift, self.tgrid, self.n, spec,
                                       self.use_kernel))
            shape = ((stream_batch,) + tuple(stream_latent_shape)
                     if spec.batched else tuple(stream_latent_shape))
            live = jax.ShapeDtypeStruct((stream_batch,) if spec.batched
                                        else (), jnp.bool_)
            records.append(ProgramRecord(
                f"stream[K={spec.num_cores},i={list(spec.i_seq)},"
                f"rtol={spec.rtol},batched={spec.batched}]", "stream", fn,
                (jax.ShapeDtypeStruct(shape, jnp.float32), live)))
        for src, dst in migrate_pairs:
            s_src, s_dst = src.num_slots, dst.num_slots
            records.append(ProgramRecord(
                f"migrate[{s_src}->{s_dst}]", "migrate", gather_slots,
                (_slot_state_structs(dst), _slot_state_structs(src),
                 jax.ShapeDtypeStruct((s_dst,), jnp.bool_),
                 jax.ShapeDtypeStruct((s_dst,), jnp.int32))))
        return records

    @property
    def retraces(self) -> int:
        """Grid-spec cache misses (compiles) — a read view over the
        ``executor.retraces`` counter."""
        return int(self._c_retraces.value)

    @property
    def stream_traces(self) -> int:
        """Stream-spec cache misses — view over ``executor.stream_traces``."""
        return int(self._c_stream_traces.value)

    @property
    def migration_traces(self) -> int:
        """Distinct migration shapes traced (via jax's own jit cache)."""
        probe = getattr(self._migrate, "_cache_size", None)
        return int(probe()) if callable(probe) else 0

    @property
    def kernel_path(self) -> str:
        """Which solver-step implementation serves this executor's rounds:

        * ``"fused-accept-pallas"`` — the real Pallas lowering of the fused
          step+rectify+accept kernel (``use_kernel=True`` on a TPU backend);
        * ``"fused-accept-oracle"`` — the fused round structure with the
          kernel executing as its bitwise-neutral jnp oracle
          (``use_kernel=True`` on CPU);
        * ``"jnp-unfused"`` — composed jnp ops, accept on the materialized
          output (``use_kernel=False``).
        """
        if not self.use_kernel:
            return "jnp-unfused"
        return ("fused-accept-oracle"
                if resolve_kernel_mode(self.use_kernel) is None
                else "fused-accept-pallas")

    def stats(self) -> dict:
        return {
            "retraces": self.retraces,
            "stream_traces": self.stream_traces,
            "migration_traces": self.migration_traces,
            "cached_grids": len(self._grids),
            "cached_streams": len(self._streams),
            "kernel_path": self.kernel_path,
        }
