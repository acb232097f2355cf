"""Request-level continuous-batching scheduler driven by the acc model.

The paper's executor answers "how many cores, what chunk size?" for a
parallel loop from measured ``T0`` / ``t_iter``.  Re-read for serving,
the same decision is "how many requests advance this tick, what prefill
chunk?": the *workload* is the set of currently queued tokens (remaining
prefill plus one decode token per running request), and
``AdaptiveCoreChunk.decide`` over its ``WorkloadProfile`` yields

* ``n_cores``     → how many requests' prefills advance per tick
  (devices↔batching — Eq. 7's "leave units free" becomes "don't open
  more concurrent prefills than the queue can keep efficient");
* ``chunk_elems`` → the prefill chunk size per tick (Eq. 10 with the
  T_m floor), snapped to a small bucket set so compiled shapes are
  bounded.

Timings of every prefill chunk and decode step flow back through the
executor telemetry (core/feedback.py) into the calibration cache, so the
decisions track observed drift instead of a one-shot calibration — the
continuous adaptation HPX's Smart Executors argue for.

Mechanics:

* Requests wait in an arrival queue (earliest-deadline-first, FIFO among
  equal deadlines), are admitted when a cache slot frees up
  (serve/kv_cache.py), prefill chunk-by-chunk, then decode greedily.
* Decode runs **one compiled step for the whole slot pool** regardless of
  which slots are active: per-slot positions ride in as an array, lanes
  are vmapped, and inactive lanes' cache writes are masked out — so
  requests of any length mix in one batch with zero recompilation and
  zero cache reallocation.
* With ``dispatch_depth`` set, decode runs through the **fused
  on-device loop** (serve/decode_loop.py): up to ``k`` tokens per
  dispatch with donated cache buffers, the host pipelined one dispatch
  ahead of the device and emitted tokens drained asynchronously — the
  per-token ``block_until_ready`` + ``device_get`` of the per-tick path
  disappears.  ``k`` is an ExecutionModel decision
  (``serve_dispatch_depth``): the measured host overhead per tick is
  the Overhead Law's T0, the measured device time per token its
  t_iter, and the depth is the chunk that amortises one to the other.
* With ``mesh`` set (launch/mesh.make_serve_mesh), the whole serving
  path runs sharded over a device mesh: weights tensor-parallel over
  'model' within each replica, the slot pool's batch dim data-parallel
  across replicas, and the global active-lane count capped by a
  ``serve_mesh_batch`` engine decision — per-device batch width is the
  paper's cores question at mesh scale
  (``global_batch = n_replicas * per_device_batch``).
* Everything is deterministic under ``SequentialExecutor`` (tick trace is
  a pure function of arrivals), which is what the tests pin down; the
  fused path emits token-identical output (greedy decode over the same
  per-lane step — see decode_loop.make_lane_step).
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import itertools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import overhead_law, strict
from ..core.acc import AdaptiveCoreChunk
from ..core.executor import Chunk, SequentialExecutor
from ..core.feedback import tag_workload
from ..core.future import Future, when_all
from ..core.model import (DEFAULT_SPEC_ACCEPT, DecisionKey, ExecutionModel,
                          decision_overhead_s, hardware_key)
from ..core.properties import params_of
from ..models import flags, lm
from ..train.autotune import serve_profiles
from . import spans
from .decode_loop import (DEFAULT_MAX_DEPTH, DEFAULT_SPEC_HISTORY,
                          SPEC_DEPTH_CANDIDATES, _check_spec_arch,
                          make_fused_decode_step, make_lane_step,
                          make_paged_decode_step, make_paged_spec_decode_step,
                          make_spec_decode_step, masked_merge)
from .kv_cache import PagedKVCachePool, SlotKVCachePool

DEFAULT_PAGE_CANDIDATES = (8, 16, 32, 64)

DEFAULT_CHUNK_BUCKETS = (8, 16, 32, 64, 128, 256)

# Under ``speculate="auto"``, depth 1 would be absorbing: no spec
# dispatches run, so the acceptance EMA can never move and the decision
# can never climb back.  Every this-many dispatches while parked at
# depth 1, one window runs at width 2 as an exploration probe — it
# refreshes the acceptance EMA at a bounded tax (one wider verify per
# SPEC_PROBE_EVERY windows) and is byte-identical like any spec step.
SPEC_PROBE_EVERY = 16

# What a slot did in one iteration of the fused decode loop, counted at
# each dispatch (``ServeScheduler.lane_iters``; the ``serve.dispatch``
# span carries the same counts as ``lane_<kind>`` attrs):
#   decode      decoding and producing a token (sums to ``take``)
#   clamped     decoding, but its budget ran out before the loop's end
#   prefill     holding a request still in prefill
#   free_queued free while requests wait after this tick's admission
#   free_idle   free with nothing waiting
LANES = ("decode", "clamped", "prefill", "free_queued", "free_idle")
_LANE_ATTRS = tuple(f"lane_{kind}" for kind in LANES)


class PromptTooLongError(ValueError):
    """A submitted prompt does not fit a cache slot.  Typed (the front
    end turns it into a structured per-request rejection instead of a
    serve-loop crash); subclasses ``ValueError`` so pre-existing callers
    that caught the bare error keep working."""

    def __init__(self, prompt_len: int, max_len: int):
        self.prompt_len = prompt_len
        self.max_len = max_len
        super().__init__(
            f"prompt of {prompt_len} tokens does not fit a "
            f"max_len={max_len} slot")


def percentile(xs, p: float) -> float:
    """Latency-report percentile; NaN on empty (shared by the launch CLI
    and the throughput benchmark so their numbers can't diverge)."""
    import numpy as np

    return float(np.percentile(np.asarray(xs), p)) if len(xs) else \
        float("nan")


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    DONE = "done"
    CANCELLED = "cancelled"    # caller withdrew the request mid-flight
    SHED = "shed"              # deadline expired before prefill: dropped


# States a request never leaves (its slot, if any, is back in the pool).
TERMINAL_STATES = (RequestState.DONE, RequestState.CANCELLED,
                   RequestState.SHED)


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle bookkeeping."""

    rid: int
    tokens: jax.Array               # (S,) int32 prompt
    max_new_tokens: int
    arrival: float
    deadline: float | None = None
    state: RequestState = RequestState.WAITING
    slot: int | None = None
    prefilled: int = 0              # prompt tokens already in the cache
    out: list[int] = dataclasses.field(default_factory=list)
    # Tokens dispatched to the device but not yet drained to ``out``
    # (fused decode path): the scheduling budget counts them, the
    # emitted output gains them only when their buffer is harvested.
    pending_out: int = 0
    admitted_at: float | None = None
    first_token_at: float | None = None
    finished_at: float | None = None
    # The scheduler-clock time each token of ``out`` landed on the host:
    # the prefill's first-token fetch, then each fused dispatch's drain.
    landed_at: list[float] = dataclasses.field(default_factory=list)
    # Host-side prompt tokens, captured at submit() time (outside the
    # strict-mode transfer guard) — the paged pool's prefix-cache key.
    host_tokens: tuple | None = None

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def remaining_prefill(self) -> int:
        return self.prompt_len - self.prefilled


@dataclasses.dataclass(frozen=True)
class TickRecord:
    """What one scheduler tick did (the determinism tests compare these)."""

    tick: int
    admitted: tuple[int, ...]
    prefill_ops: tuple[tuple[int, int], ...]   # (rid, tokens advanced)
    decoded: tuple[int, ...]
    finished: tuple[int, ...]
    queued_tokens: int
    n_cores: int
    chunk: int
    depth: int = 0       # fused dispatch depth (0: per-tick decode path)
    # SLO accounting (the deterministic trace tests assert these):
    # deadline misses charged to this tick (sheds + late finishes) and
    # the waiting-queue depth left after this tick's admission.
    deadline_misses: int = 0
    queue_depth: int = 0
    # Variable tokens-per-dispatch accounting: ``depth`` is the decided
    # per-lane budget, but under speculation a loop round emits a
    # variable accepted-token count, so the total tokens this tick's
    # dispatch carried is recorded explicitly instead of being inferred
    # as lanes × depth.  ``spec_depth`` is the speculation width the
    # dispatch ran with (0: speculation off).
    dispatched_tokens: int = 0
    spec_depth: int = 0


class ServeScheduler:
    """Continuous batching over a slot pool, acc-decided per tick."""

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int,
                 max_len: int, window: int | None = None,
                 executor=None, acc: AdaptiveCoreChunk | None = None,
                 chunk_buckets: Sequence[int] = DEFAULT_CHUNK_BUCKETS,
                 clock: Callable[[], float] = time.perf_counter,
                 kernel_tuner=None,
                 dispatch_depth: int | str | None = None,
                 max_dispatch_depth: int = DEFAULT_MAX_DEPTH,
                 pipeline: int = 2, sync_every: int = 8,
                 admission: str = "greedy",
                 shed_expired: bool = False, mesh=None,
                 paged: bool = False, page_size: int | str = "auto",
                 prefill_interleave: int | str = "auto",
                 speculate: int | str | None = None,
                 max_spec_depth: int = 8,
                 spec_history: int = DEFAULT_SPEC_HISTORY):
        kinds = set(cfg.layer_kinds())
        if "cross_attn" in kinds:
            raise ValueError(
                "ServeScheduler does not serve cross-attention archs "
                "(per-request frontend feats); use ServeEngine's legacy "
                "batch path")
        self.cfg = cfg
        self.params = params
        self.window = window if window is not None else cfg.attn_window
        if self.window is not None and self.window <= 0:
            self.window = None
        self.max_len = max_len
        self.executor = executor if executor is not None \
            else SequentialExecutor()
        self.acc = acc or params_of(self.executor) or AdaptiveCoreChunk()
        # Measured Pallas blocks for the compiled prefill/decode steps
        # (kernels/autotune.KernelTuner); None = analytic/jnp paths.  The
        # tuner runs at jit-trace time, so each compiled shape pays at
        # most one candidate search — and none when the winner is already
        # persisted in the calibration store.
        self.kernel_tuner = kernel_tuner
        # Mesh-sharded serving (launch/mesh + launch/sharding): weights
        # go tensor-parallel over 'model' within each replica (serving
        # drops 'data' from the weight rules — full TP copy per
        # replica), the slot pool's batch dim splits into data-parallel
        # groups, and every compiled step (prefill, decode, the fused
        # loop) partitions over the committed input placements.
        self.mesh = mesh
        self.n_replicas = 1
        self.mesh_desc = None
        if mesh is not None:
            from ..launch import mesh as mesh_lib
            from ..launch import sharding as sharding_lib

            self.n_replicas = mesh_lib.n_data_replicas(mesh)
            if n_slots % self.n_replicas:
                raise ValueError(
                    f"n_slots={n_slots} must divide into "
                    f"{self.n_replicas} data-parallel replicas "
                    f"(mesh {dict(mesh.shape)})")
            self.mesh_desc = "x".join(
                str(mesh.shape[a]) for a in mesh.axis_names)
            pshard, _ = sharding_lib.serve_shardings(
                cfg, mesh, params, n_slots, max_len)
            self.params = jax.device_put(params, pshard)
        self.slots_per_replica = n_slots // self.n_replicas
        # Request stamps (arrival, admission, tokens, finish).  The
        # default is the clock of serve/spans.py and the profiler trace.
        self.clock = clock
        self.chunk_buckets = tuple(sorted(set(int(b) for b in chunk_buckets
                                              if b > 0))) or (max_len,)
        # Padding prefill chunks to a bucket is only sound where every
        # layer leaves the pad tokens out: attention masks them by
        # position, and a Mamba-2 layer is told the chunk's real length
        # and leaves its state as the real tokens left it.  xLSTM states
        # would absorb the pads, and ring (SWA) writes could wrap over
        # live entries — those archs run exact-size chunks instead.
        self._pad_ok = self.window is None and kinds <= {
            "attn", "shared_attn", "mamba2"}
        self.prefill_profile, self.decode_profile = serve_profiles(cfg)
        # Workload keys carry the model's shape, not just its name:
        # cfg.reduced() keeps the name, and a persisted t_iter smoothed on
        # the tiny config must never drive decisions for the full one.
        sig = (cfg.name, cfg.d_model, cfg.n_layers)
        self.prefill_key = ("serve_prefill",) + sig
        self.decode_key = ("serve_decode",) + sig
        # Paged KV pool (kv_cache.PagedKVCachePool): memory layout as an
        # ExecutionModel decision.  The page size is decided at
        # construction (``serve_page_size`` — geometry is baked into the
        # compiled steps), seeded analytically from whatever the store
        # already knows and re-decided on timed syncs as the run observes
        # real page-management and prefill costs; the refined choice
        # drives the *next* pool this store backs.  The prefill/decode
        # interleave ratio (``serve_prefill_interleave``) is decided per
        # tick — see ``_decide_interleave``.
        self.paged = bool(paged)
        self.page_size_key = DecisionKey("serve_page_size", sig)
        self.interleave_key = DecisionKey("serve_prefill_interleave", sig)
        self.page_mgmt_key = ("serve_page_mgmt",) + sig
        if isinstance(prefill_interleave, str):
            if prefill_interleave != "auto":
                raise ValueError(
                    f"prefill_interleave must be an int or 'auto'; "
                    f"got {prefill_interleave!r}")
        else:
            prefill_interleave = max(int(prefill_interleave), 1)
        self.prefill_interleave = prefill_interleave
        # Decode lanes stalled on prefill: cumulative seconds the tick
        # blocked on prefill chunks while decode lanes were active with
        # no fused dispatch in flight to hide behind — the number the
        # interleave decision minimises (benchmarks/serve_throughput.py
        # surfaces it per tick).
        self.prefill_stall_s = 0.0
        self._last_depth = 0
        self._page_size_auto = paged and page_size == "auto"
        if self.paged:
            if dispatch_depth is None:
                raise ValueError(
                    "paged serving requires the fused decode path: "
                    "pass dispatch_depth (an int or 'auto')")
            if isinstance(page_size, str):
                if page_size != "auto":
                    raise ValueError(
                        f"page_size must be an int or 'auto'; "
                        f"got {page_size!r}")
                ps = self._decide_page_size()
            else:
                ps = max(int(page_size), 1)
                model = self.decision_model()
                if model is not None:
                    model.note(self.page_size_key,
                               policy="fixed-page-size", cores=1,
                               chunk=ps, inputs=(("fixed", True),))
            self.pool: Any = PagedKVCachePool(
                cfg, n_slots, max_len, window=self.window,
                page_size=ps, mesh=mesh)
        else:
            self.pool = SlotKVCachePool(cfg, n_slots, max_len,
                                        window=self.window, mesh=mesh)
        # Engine key for the per-tick decision: every tick's width/chunk
        # choice lands in the shared ExecutionModel trace under this key
        # (--explain-decisions attributes serve ticks through it).
        self.tick_key = DecisionKey("serve_tick", sig)
        # Fused decode hot path (serve/decode_loop.py).  ``dispatch_depth``
        # is None (per-tick decode, one device round-trip per token),
        # an int (fixed depth), or "auto" (per-tick engine decision of
        # kind ``serve_dispatch_depth``).
        if isinstance(dispatch_depth, str):
            if dispatch_depth != "auto":
                raise ValueError(
                    f"dispatch_depth must be None, an int, or 'auto'; "
                    f"got {dispatch_depth!r}")
        elif dispatch_depth is not None:
            dispatch_depth = max(int(dispatch_depth), 1)
        self.dispatch_depth = dispatch_depth
        self._fused = dispatch_depth is not None
        self.max_dispatch_depth = max(int(max_dispatch_depth), 1)
        self.pipeline = max(int(pipeline), 1)
        self.sync_every = max(int(sync_every), 1)
        self.depth_key = DecisionKey("serve_dispatch_depth", sig)
        # Self-speculative decoding (decode_loop.make_spec_decode_step):
        # ``speculate`` is None (off), an int (fixed draft window), or
        # "auto" (decision kind ``serve_spec_depth`` — analytic prior
        # from the overhead law's accept-vs-verify-cost trade, refined
        # online from the acceptance rate observed at drain time, with
        # backoff to depth 1 when acceptance collapses).  Speculation
        # rides the fused path; rollback of rejected drafts is pure
        # position bookkeeping only under position-masked attention, so
        # _check_spec_arch gates out SWA rings and recurrent state.
        if isinstance(speculate, str):
            if speculate != "auto":
                raise ValueError(
                    f"speculate must be None, an int, or 'auto'; "
                    f"got {speculate!r}")
        elif speculate is not None:
            speculate = max(int(speculate), 1)
        self.speculate = speculate
        self._spec = speculate is not None
        if self._spec:
            if dispatch_depth is None:
                raise ValueError(
                    "speculative decoding rides the fused decode path: "
                    "pass dispatch_depth (an int or 'auto')")
            _check_spec_arch(cfg, self.window)
        self.max_spec_depth = max(int(max_spec_depth), 1)
        self.spec_history = max(int(spec_history), 8)
        self.spec_depth_key = DecisionKey("serve_spec_depth", sig)
        # Acceptance EMA: each drained spec dispatch contributes the
        # acceptance rate recovered at *its own* width (elems=verifies,
        # "seconds"=accept × verifies, so the refiner's per-element
        # ratio is the acceptance itself — floored at 1e-3 through
        # total-rejection stretches so the sample still records); plus
        # seconds per speculative loop round, the depth decision's cost
        # input.
        self.spec_accept_key = ("serve_spec_accept",) + sig
        self.spec_step_key = ("serve_spec_step",) + sig
        # Clean width-1 per-iteration seconds (non-speculative timed
        # dispatches only; ``fused_key`` is per-token and keeps being
        # observed under speculation for the window decisions).  The
        # ratio spec_step / fused_iter prices the verify width online —
        # the depth decision's width_cost stops being a static prior as
        # soon as both EMAs hold samples.
        self.fused_iter_key = ("serve_fused_iter",) + sig
        self._spec_jit: dict[int, Any] = {}
        self._dev_hist = None       # device-resident token-history ring
        self._hist_overrides: dict[int, list[int]] = {}
        self.spec_verifies = 0      # per-lane verify events drained
        self.spec_emitted = 0       # tokens emitted by speculative steps
        self.spec_rounds = 0        # speculative loop rounds drained
        self._spec_depth = 1
        # Admission policy: "greedy" fills every free slot (the pre-SLO
        # behaviour, what the deterministic trace tests pin); "adaptive"
        # makes the width a ``serve_admission`` engine decision from the
        # queue depth and the measured tick time (the front end's mode).
        if admission not in ("greedy", "adaptive"):
            raise ValueError(
                f"admission must be 'greedy' or 'adaptive', "
                f"got {admission!r}")
        self.admission = admission
        self.admit_key = DecisionKey("serve_admission", sig)
        # Mesh-aware batch width (decision kind ``serve_mesh_batch``):
        # the DecisionKey's hardware field is extended with the mesh
        # shape, so a width chosen on a (4,2) mesh never backs a (2,4)
        # run on the same silicon.
        self.mesh_key = None if mesh is None else DecisionKey(
            "serve_mesh_batch", sig,
            hardware=f"{hardware_key()}|mesh={self.mesh_desc}")
        # Deadline enforcement: with ``shed_expired`` a WAITING request
        # whose deadline has already passed is shed *before* prefill
        # (its tokens would be thrown away anyway); finished requests
        # that land past their deadline are counted as misses either
        # way.  Cumulative SLO counters (per-tick values ride on the
        # TickRecord):
        self.shed_expired = bool(shed_expired)
        self.deadline_misses = 0    # sheds + late finishes
        self.shed = 0               # expired before prefill, dropped
        self.cancelled = 0          # withdrawn by the caller mid-flight
        self._tick_misses = 0       # misses charged to the current tick
        self._queue_depth = 0       # waiting after this tick's admission
        # Timing keys for the depth decision's two inputs (both refined
        # online): seconds of host work per tick, seconds of device
        # work per fused-decoded token.
        self.host_tick_key = ("serve_host_tick",) + sig
        self.fused_key = ("serve_decode_fused",) + sig
        self._fused_jit = None
        self._warm_fused = False
        # Compiled fused-step variants ("fused" or ("spec", d)) that have
        # executed at least once — the timed-sync guard checks membership
        # so a cold compile is never recorded as dispatch time.
        self._warm_steps: set = set()
        self._dev_toks = None       # device-resident last-token carry
        self._tok_overrides: dict[int, int] = {}
        # In-flight fused dispatches: (out_buf, [(req, slot, take)...]).
        self._inflight: collections.deque = collections.deque()
        # Dispatch-granularity telemetry (benchmarks/serve_throughput.py
        # derives host-overhead-per-token and dispatches-per-token).
        self.decode_dispatches = 0
        self.decode_tokens = 0
        # Decode loop iterations executed (fused: max take per dispatch
        # — the fori_loop trip count; per-tick: 1 per dispatch).  This is
        # the multiplier for decode_cost_analysis()'s per-iteration
        # flops/bytes in the benchmark's TFLOP/s + HBM-BW accounting.
        self.decode_loop_iters = 0
        # Fused decode: every loop iteration counts each slot once under
        # one of ``LANES`` (the five sum to n_slots per iteration).
        self.lane_iters = dict.fromkeys(LANES, 0)
        self.host_roundtrips = 0    # block/device_get events, all paths
        self.host_overhead_s = 0.0  # tick wall-clock minus device waits
        self._blocked_s = 0.0
        self._rid = itertools.count()
        self._waiting: list[Request] = []
        self._active: list[Request] = []
        self.requests: dict[int, Request] = {}
        self.trace: list[TickRecord] = []
        self._tick = 0
        self._prefill_jit: dict[int, Any] = {}
        self._decode_jit = None
        # Shapes that have executed at least once: a cold call pays XLA
        # compilation, and seconds of compile time must never be recorded
        # as t_iter (it would seed — and persist — a poisoned EMA).
        self._warm_prefill: set[int] = set()
        self._warm_decode = False
        if self._spec:
            self._spec_depth = self._decide_spec_depth()

    # ------------------------------------------------------------------ API
    def submit(self, tokens, max_new_tokens: int = 16, *,
               deadline: float | None = None,
               arrival: float | None = None) -> int:
        """Enqueue a request; returns its id.  ``tokens`` is a 1-D prompt.

        The prompt must fit the slot: prompt + generated tokens are capped
        by the pool's ``max_len``.
        """
        tokens = jnp.asarray(tokens, jnp.int32).reshape(-1)
        if tokens.shape[0] == 0:
            raise ValueError("empty prompt")
        if tokens.shape[0] >= self.max_len:
            raise PromptTooLongError(int(tokens.shape[0]), self.max_len)
        rid = next(self._rid)
        req = Request(rid=rid, tokens=tokens,
                      max_new_tokens=max(int(max_new_tokens), 1),
                      arrival=self.clock() if arrival is None else arrival,
                      deadline=deadline)
        if self._spec or (self.paged
                          and getattr(self.pool, "prefix_cache", False)):
            # Prefix-cache key / speculation history seed, captured here
            # — outside the tick's strict-mode transfer guard (submit is
            # the sanctioned spot for a prompt to touch the host).
            import numpy as np

            req.host_tokens = tuple(
                int(t) for t in np.asarray(tokens))
        self.requests[rid] = req
        self._waiting.append(req)
        return rid

    @property
    def pending(self) -> int:
        """Requests not yet finished (waiting + running)."""
        return len(self._waiting) + len(self._active)

    def cancel(self, rid: int) -> bool:
        """Withdraw a request mid-flight.  Its cache slot goes straight
        back to the free list (no reallocation — the pool's
        ``allocations==1`` donation invariant holds), and any tokens it
        has in a not-yet-drained fused dispatch are dropped at drain
        time instead of emitted.  Returns False when the request is
        unknown or already terminal (cancel is idempotent)."""
        req = self.requests.get(rid)
        if req is None or req.state in TERMINAL_STATES:
            return False
        if req.state is RequestState.WAITING:
            self._waiting.remove(req)
        else:
            self._active.remove(req)
            if req.slot is not None:
                # A first token the prefill already produced for this
                # slot must not be spliced into the next dispatch's
                # token carry — the slot may belong to someone else by
                # then.
                self._tok_overrides.pop(req.slot, None)
                self._hist_overrides.pop(req.slot, None)
                self.pool.release(req.slot)
                req.slot = None
        req.state = RequestState.CANCELLED
        req.finished_at = self.clock()
        self._record_request(req)
        self.cancelled += 1
        return True

    def decision_model(self) -> ExecutionModel | None:
        """The ExecutionModel engine behind this scheduler's decisions
        (None when the params object carries no calibration cache, e.g.
        StaticCoreChunk)."""
        cache = getattr(self.acc, "cache", None)
        return ExecutionModel.of(cache) if cache is not None else None

    def results(self) -> dict[int, list[int]]:
        self.flush()   # fused path: land every dispatched-but-undrained token
        return {rid: list(r.out) for rid, r in self.requests.items()
                if r.state is RequestState.DONE}

    def clear_finished(self) -> None:
        """Drop completed requests and the tick trace.  Long-lived
        callers (the ServeEngine facade) call this after draining —
        otherwise every prompt and TickRecord ever served stays
        reachable."""
        self.flush()   # a DONE request's tokens may still be in flight
        self.requests = {rid: r for rid, r in self.requests.items()
                         if r.state not in TERMINAL_STATES}
        self.trace.clear()

    def run_until_idle(self, max_ticks: int = 100_000) -> dict[int, list[int]]:
        for _ in range(max_ticks):
            if not self.pending:
                return self.results()
            self.tick()
        raise RuntimeError(f"scheduler did not drain in {max_ticks} ticks")

    def warmup(self) -> None:
        """Compile everything the steady-state tick path touches — the
        decode step, the prefill buckets, the donated slot write-back
        and the first-token argmax — so the first timed tick measures
        compute, not compilation."""
        if self._fused:
            # One compile serves every depth (dynamic trip count); the
            # zero-step calls donate and return the pool unchanged.
            self._tok_overrides[0] = 0   # compile the override splice
            zeros = jnp.zeros(self.pool.n_slots, jnp.int32)
            new_caches, out_buf, toks = self._fused_step()(
                self.params, self.pool.caches,
                *((self.pool.page_table_array(),) if self.paged else ()),
                self._decode_toks(), self.pool.positions_array(), zeros)
            self.pool.mark_donated("fused decode warmup")
            jax.block_until_ready(out_buf)
            self.pool.adopt(new_caches)
            self._dev_toks = toks
            self._warm_steps.add("fused")
            # Speculative variants: compile every width the adaptive
            # re-decision can land on (plus the plain fused step above,
            # which backoff-to-1 falls back to) — a mid-run width
            # switch must swap executables, never compile one.  Each
            # zero-step call's while cond is False, so nothing runs;
            # the history-override splice compiles along the way.
            if self._spec:
                cap = min(self.max_spec_depth, self.max_dispatch_depth)
                if self.speculate == "auto":
                    widths = [c for c in SPEC_DEPTH_CANDIDATES
                              if 2 <= c <= cap]
                else:
                    widths = [self._spec_depth] \
                        if self._spec_depth >= 2 else []
                for d in widths:
                    self._hist_overrides[0] = [0]
                    new_caches, hist, out_buf, toks, _stats = \
                        self._spec_step(d)(
                            self.params, self.pool.caches,
                            *((self.pool.page_table_array(),)
                              if self.paged else ()),
                            self._decode_hist(), self._decode_toks(),
                            self.pool.positions_array(), zeros)
                    self.pool.mark_donated("fused decode warmup")
                    jax.block_until_ready(out_buf)
                    self.pool.adopt(new_caches)
                    self._dev_toks = toks
                    self._dev_hist = hist
                    self._warm_steps.add(("spec", d))
            self._warm_fused = True
        else:
            self._decode_step()(
                self.params, self.pool.caches,
                jnp.zeros(self.pool.n_slots, jnp.int32),
                self.pool.positions_array(),
                jnp.zeros(self.pool.n_slots, dtype=bool))
            self._warm_decode = True
        if self._pad_ok:
            warmed, warm_b = None, 0
            for b in self.chunk_buckets:
                if b < self.max_len:
                    row = self.pool.read_slot(0)
                    warmed = self._prefill_step(b)(
                        self.params, row, jnp.zeros((1, b), jnp.int32),
                        jnp.int32(0), jnp.int32(b - 1))
                    self._warm_prefill.add(b)
                    warm_b = b
            if warmed is not None:
                # Slot 0 is free here (warmup precedes admission) and
                # masking hides the garbage row: writing it back
                # compiles the donated write-back and the first-token
                # argmax the real prefill path goes through.
                logits, new_row = warmed
                int(jnp.argmax(logits[0, 0]))
                if self.paged:
                    # Unmapped table → the garbage row scatters into the
                    # scratch page; compiles the ranged page write.
                    self.pool.write_slot(0, new_row, 0, warm_b)
                else:
                    self.pool.write_slot(0, new_row)

    # ----------------------------------------------------------------- tick
    def tick(self) -> TickRecord:
        """One scheduler round: admit → decide → prefill chunks → decode.

        The wall-clock of everything that is *not* a device wait is
        accumulated as ``host_overhead_s`` — the per-dispatch T0 the
        fused path amortises: the ``serve.tick`` span less the waits
        inside it (``_blocked_s``: the stretch of each prefill's chunk
        thunks, drains that blocked, synced launches).  On fused
        decode-only ticks it is also
        folded into the calibration store (``serve_host_tick``), which
        is what drives the next ``serve_dispatch_depth`` decision.

        Under strict mode (``core.strict``) the whole round runs with
        implicit device→host transfers disallowed — the sanctioned
        syncs all go through explicit ``device_get``/
        ``block_until_ready``, so anything else that blocks here is a
        bug the guard turns into a hard error.
        """
        with strict.hot_dispatch_guard():
            self._blocked_s = 0.0
            self._tick_misses = 0
            was_warm = self._warm_fused
            with spans.span("serve.tick", tick=self._tick) as sp:
                rec = self._tick_fused() if self._fused \
                    else self._tick_legacy()
            host_s = max(sp.end - sp.start - self._blocked_s, 0.0)
            self.host_overhead_s += host_s
            if self._fused and was_warm and rec.decoded \
                    and not rec.prefill_ops:
                # Clean sample: no prefill compute and no cold compiles
                # in the window, so host_s is pure scheduling overhead.
                model = self.decision_model()
                if model is not None:
                    model.observe(self.host_tick_key, 1, host_s)
            return rec

    def _tick_legacy(self) -> TickRecord:
        """Per-tick decode: one device round-trip per decoded token."""
        admitted = self._admit()
        queued, cores, chunk = self._decide()
        prefill_ops, pf_finished = self._run_prefill(cores, chunk)
        decoded, dec_finished = self._run_decode()
        finished = pf_finished + dec_finished
        self._active = [r for r in self._active
                        if r.state is not RequestState.DONE]
        rec = TickRecord(
            tick=self._tick, admitted=tuple(admitted),
            prefill_ops=tuple(prefill_ops), decoded=tuple(decoded),
            finished=tuple(finished), queued_tokens=queued,
            n_cores=cores, chunk=chunk,
            deadline_misses=self._tick_misses,
            queue_depth=self._queue_depth)
        self.trace.append(rec)
        self._tick += 1
        return rec

    def _tick_fused(self) -> TickRecord:
        """Fused decode: admission and accounting run decoupled from the
        device stream.  The tick harvests whatever finished dispatches
        are ready (blocking only to bound the pipeline), runs prefill as
        before, then dispatches the next fused decode without waiting
        for it — tick N+1's host work overlaps tick N's device work."""
        self._drain(drop_to=self.pipeline - 1, harvest=True)
        admitted = self._admit()
        pf_pending = any(r.state is RequestState.PREFILL
                         for r in self._active)
        n_dec = sum(1 for r in self._active
                    if r.state is RequestState.DECODE)
        if pf_pending:
            queued, cores, chunk = self._decide()
            if self.paged and n_dec:
                # Chunked-prefill interleave: cap this tick's prefill
                # chunk-ops to what fits the window the in-flight fused
                # decode keeps the device busy (``serve_prefill_interleave``).
                cores = min(cores, self._decide_interleave(chunk))
            pre_blocked = self._blocked_s
            prefill_ops, pf_finished = self._run_prefill(cores, chunk)
            if n_dec and not self._inflight:
                # Decode lanes sat idle while these chunks ran — nothing
                # was in flight to hide the prefill behind.  This is the
                # stall the interleave decision minimises.
                self.prefill_stall_s += max(
                    self._blocked_s - pre_blocked, 0.0)
        else:
            # Decode-only tick: skip the prefill width/chunk query — on
            # the fused hot path those engine calls are host overhead.
            queued = n_dec
            cores, chunk = 0, 0
            prefill_ops, pf_finished = [], []
        decoded, dec_finished, depth, disp_toks, spec_d = \
            self._dispatch_decode()
        finished = pf_finished + dec_finished
        self._active = [r for r in self._active
                        if r.state is not RequestState.DONE]
        if not self._active and not self._waiting:
            # Going idle: nothing left to overlap the pipeline with, so
            # land every in-flight token now — finished_at must mean
            # "tokens on the host", not "whenever the next tick drains".
            self.flush()
        rec = TickRecord(
            tick=self._tick, admitted=tuple(admitted),
            prefill_ops=tuple(prefill_ops), decoded=tuple(decoded),
            finished=tuple(finished), queued_tokens=queued,
            n_cores=cores, chunk=chunk, depth=depth,
            deadline_misses=self._tick_misses,
            queue_depth=self._queue_depth,
            dispatched_tokens=disp_toks, spec_depth=spec_d)
        self.trace.append(rec)
        self._tick += 1
        return rec

    def _admit(self) -> list[int]:
        """Earliest-deadline-first admission into free slots; FIFO among
        requests without deadlines.  Exhausted pool ⇒ requests keep
        waiting (they are *queued*, never dropped — unless
        ``shed_expired`` and their deadline has already passed, in which
        case prefilling them would burn compute on tokens nobody can
        use: they are shed before prefill and counted as misses).  With
        ``admission="adaptive"`` the number of slots filled this tick is
        a ``serve_admission`` engine decision, not "all of them"."""
        with spans.span("serve.admit") as sp:
            admitted = self._admit_waiting()
            sp.attrs["admitted"] = len(admitted)
            sp.attrs["queue"] = self._queue_depth
        return admitted

    def _admit_waiting(self) -> list[int]:
        if self.shed_expired and self._waiting:
            now = self.clock()
            kept = []
            for req in self._waiting:
                if req.deadline is not None and now > req.deadline:
                    req.state = RequestState.SHED
                    req.finished_at = now
                    self._record_request(req)
                    self.shed += 1
                    self.deadline_misses += 1
                    self._tick_misses += 1
                else:
                    kept.append(req)
            self._waiting = kept
        self._waiting.sort(key=lambda r: (
            r.deadline if r.deadline is not None else float("inf"),
            r.arrival, r.rid))
        width = self._decide_admission()
        lane_cap = self._decide_mesh_batch()
        admitted = []
        now = None
        while self._waiting and self.pool.free_slots() \
                and (width is None or len(admitted) < width) \
                and (lane_cap is None or len(self._active) < lane_cap):
            req = self._waiting.pop(0)
            if self.paged and req.host_tokens is not None:
                # Map any cached prefix of the prompt read-only into the
                # slot's page table; prefill resumes past it.
                req.slot, reused = self.pool.acquire_with_prefix(
                    req.rid, req.host_tokens)
                req.prefilled = reused
            else:
                req.slot = self.pool.acquire(req.rid)
            if now is None:
                now = self.clock()
            req.admitted_at = now
            req.state = RequestState.PREFILL
            self._active.append(req)
            admitted.append(req.rid)
        self._queue_depth = len(self._waiting)
        return admitted

    def _decide_admission(self) -> int | None:
        """Admission width for this tick (decision kind
        ``serve_admission``), or None for greedy fill-every-slot.

        The analytic prior reads the Overhead Law at the request level:
        the measured host tick time is the T0 every admission round
        pays, one queued request's prefill bill (online-refined
        ``serve_prefill`` t_iter × its remaining prompt) is the t_iter,
        and the queue depth is the element count — the width is the
        widest admission that keeps the tick efficient, opened up to
        every free slot when the head-of-queue deadline slack is inside
        two admission rounds (deadline pressure beats efficiency).
        """
        if self.admission != "adaptive" or not self._waiting:
            return None
        free = self.pool.free_slots()
        if free == 0:
            return None
        model = self.decision_model()
        if model is None:       # static params object: no store, greedy
            return None
        with spans.span("serve.decide", kind="admission"):
            host = model.smoothed_t_iter(self.host_tick_key)
            inputs: tuple = ()
            if host is None:
                # Same seed as the depth decision: the calibrated
                # empty-dispatch T0 plus a few engine queries — the host
                # work a tick provably pays before any tick was timed.
                host = self.acc.calibrate_t0(self.executor) \
                    + 4.0 * decision_overhead_s()
                inputs = (("seeded", True),)
            head = self._waiting[0]
            t_pf = self.acc.measure_iteration(
                self.executor, self.prefill_profile,
                max(head.remaining_prefill, 1), key=self.prefill_key)
            req_cost = t_pf * max(head.remaining_prefill, 1)
            slack = None if head.deadline is None \
                else head.deadline - self.clock()
            decision = model.admission_width(
                self.admit_key, queue_depth=len(self._waiting),
                free_slots=free, host_tick_s=host, request_cost_s=req_cost,
                slack_s=slack, max_width=self.pool.n_slots,
                eff=getattr(self.acc, "efficiency",
                            overhead_law.DEFAULT_EFFICIENCY),
                evidence=(self.host_tick_key, self.prefill_key),
                inputs=inputs)
        return decision.cores

    def _decide_mesh_batch(self) -> int | None:
        """Global active-lane cap for a mesh-sharded pool (decision kind
        ``serve_mesh_batch``), or None when serving single-device / the
        queue is empty / the params object carries no store.

        Per-device batch width is the mesh's cores/chunk question: the
        engine amortises the measured per-dispatch host overhead
        (``serve_host_tick``) against the measured fused device step
        (``serve_decode_fused``) over the per-replica demand, and the
        cap is ``width * n_replicas`` — admission never opens more
        concurrent lanes per replica than the dispatch can keep
        efficient.  Only consulted when there is something to admit, so
        decode-only ticks pay no engine query."""
        if self.mesh is None or not self._waiting:
            return None
        model = self.decision_model()
        if model is None:       # static params object: every slot
            return None
        with spans.span("serve.decide", kind="mesh_batch"):
            demand = len(self._waiting) + len(self._active)
            evidence = [self.host_tick_key, self.fused_key]
            inputs: tuple = (("mesh", self.mesh_desc),)
            host = model.smoothed_t_iter(self.host_tick_key)
            if host is None:
                host = self.acc.calibrate_t0(self.executor) \
                    + 4.0 * decision_overhead_s()
                inputs += (("seeded", True),)
            dev = model.smoothed_t_iter(self.fused_key)
            if dev is None:
                dev = self.acc.measure_iteration(
                    self.executor, self.decode_profile, max(demand, 1),
                    key=self.decode_key)
                evidence.append(self.decode_key)
            decision = model.mesh_batch(
                self.mesh_key, demand=demand, n_replicas=self.n_replicas,
                slots_per_replica=self.slots_per_replica,
                host_tick_s=host, device_step_s=dev,
                eff=getattr(self.acc, "efficiency",
                            overhead_law.DEFAULT_EFFICIENCY),
                evidence=tuple(evidence), inputs=inputs)
        return decision.batch_width

    def _decide(self) -> tuple[int, int, int]:
        """(queued tokens, batch width, prefill chunk) for this tick.

        Spoken through the three customization points so any
        execution-parameters object plugs in: ``AdaptiveCoreChunk`` gives
        the Overhead-Law decision, ``StaticCoreChunk`` the fixed
        OpenMP-static split.  The queue's t_iter is the token-weighted
        mix of the prefill and decode regimes — each priced by its own
        profile, each overridden by its own online-feedback key once the
        executor has timed real chunks of that kind.
        """
        pf_tokens = sum(r.remaining_prefill for r in self._active
                        if r.state is RequestState.PREFILL)
        dec_tokens = sum(1 for r in self._active
                         if r.state is RequestState.DECODE)
        queued = pf_tokens + dec_tokens
        if queued <= 0:
            return 0, 0, 0
        with spans.span("serve.decide", kind="tick"):
            t_pf = self.acc.measure_iteration(
                self.executor, self.prefill_profile, max(pf_tokens, 1),
                key=self.prefill_key)
            t_dec = self.acc.measure_iteration(
                self.executor, self.decode_profile, max(dec_tokens, 1),
                key=self.decode_key)
            t_iter = (pf_tokens * t_pf + dec_tokens * t_dec) / queued
            if hasattr(self.acc, "decide"):
                # One engine query per tick: cores + chunk in a single traced
                # decision (equivalent to the two customization-point calls
                # below — decide() is what both of them derive from).
                d = self.acc.decide(self.executor, t_iter, queued,
                                    key=self.tick_key,
                                    evidence=(self.prefill_key,
                                              self.decode_key))
                cores, chunk = d.n_cores, d.chunk_elems
            else:
                cores = self.acc.processing_units_count(self.executor, t_iter,
                                                        queued)
                chunk = self.acc.get_chunk_size(self.executor, t_iter, cores,
                                                queued)
        return queued, max(cores, 1), max(chunk, 1)

    # -- prefill -------------------------------------------------------------
    def _bucket(self, step: int) -> int:
        """Smallest bucket >= step (the compiled-width set); steps above
        the largest bucket are clamped down to it."""
        for b in self.chunk_buckets:
            if b >= step:
                return b
        return self.chunk_buckets[-1]

    def _segment(self, req: Request, chunk: int) -> int:
        """Next prefill piece for ``req``: the decided chunk, clamped to
        the remaining prompt, never crossing a ring-buffer (SWA) window
        boundary, and never wider than the largest compile bucket."""
        step = min(max(chunk, 1), req.remaining_prefill,
                   self.chunk_buckets[-1])
        if self.window is not None:
            pos = self.pool.positions[req.slot]
            step = min(step, self.window - pos % self.window)
        return step

    def _prefill_step(self, length: int):
        fn = self._prefill_jit.get(length)
        if fn is None:
            cfg, window = self.cfg, self.window

            def prefill_chunk(params, row_caches, piece, pos, last):
                with flags.kernel_tuner(self.kernel_tuner
                                        or flags.KERNEL_TUNER):
                    return lm.forward_cached(params, piece, row_caches, pos,
                                             cfg, window=window,
                                             logit_index=last,
                                             length=last + 1)

            fn = jax.jit(prefill_chunk)
            self._prefill_jit[length] = fn
        return fn

    def _run_prefill(self, cores: int, chunk: int):
        ready = [r for r in self._active if r.state is RequestState.PREFILL]
        if not ready or chunk <= 0:
            return [], []
        # n_cores ↔ how many requests advance this tick (batching width).
        width = min(max(cores, 1), len(ready))
        ops = []
        for req in ready[:width]:
            step = self._segment(req, chunk)
            padded = self._bucket(step) if self._pad_ok else step
            if padded > self.max_len - req.prefilled:
                padded = step    # no room to pad: exact-size chunk
            ops.append((req, step, padded))

        if self.paged:
            # Page management is the ``serve_page_size`` decision's T0:
            # allocate/CoW the pages this wave will write, timed and fed
            # back (``serve_page_mgmt``) so the next pool's page size is
            # decided from measured cost, not the analytic prior.
            t_pg = time.perf_counter()
            for req, _, padded in ops:
                self.pool.ensure_writable(
                    req.slot, req.prefilled, req.prefilled + padded)
            model = self.decision_model()
            if model is not None:
                model.observe(self.page_mgmt_key, len(ops),
                              max(time.perf_counter() - t_pg, 0.0))

        n_dec = sum(1 for r in self._active
                    if r.state is RequestState.DECODE)
        with spans.span("serve.prefill", chunks=len(ops),
                        tokens=sum(step for _, step, _ in ops),
                        decoding=n_dec, inflight=len(self._inflight)) as sp:
            return self._prefill_ops(ops, sp.id)

    def _prefill_ops(self, ops: list, span_id: int):
        """Run the chunks ``ops`` (request, step, padded width) through
        the executor, write them back and route finished prompts into
        decode.  ``span_id``: the ``serve.prefill`` span, the parent of
        the chunk thunks' spans wherever the executor runs them."""
        pool, params = self.pool, self.params
        waits: list = []    # (start, end) of each chunk thunk

        def run_chunk(chunk: Chunk):
            req, step, padded = ops[chunk.start]
            piece = jax.lax.dynamic_slice_in_dim(
                req.tokens, req.prefilled, step)
            if padded > step:
                piece = jnp.pad(piece, (0, padded - step))
            with spans.span("serve.prefill.read_slot",
                            parent=span_id) as read:
                row = pool.read_slot(req.slot)
            # Synchronise inside the thunk: the executor times this call
            # for the feedback loop, and an async jit dispatch would
            # record microseconds of launch cost as the chunk's t_iter.
            with spans.span("serve.prefill.chunk", parent=span_id,
                            tokens=padded, pad=padded - step) as run:
                out = jax.block_until_ready(  # repro-lint: disable=RL002
                    self._prefill_step(padded)(
                        params, row, piece[None], jnp.int32(req.prefilled),
                        jnp.int32(step - 1)))
            waits.append((read.start, run.end))
            return out

        # Feedback only sees warm shapes: a tick whose ops include a
        # never-executed chunk width runs untimed (it compiles).
        if all(padded in self._warm_prefill for _, _, padded in ops):
            tag_workload(run_chunk, self.prefill_key)
        futs = self.executor.bulk_async_execute(
            run_chunk, [Chunk(i, step) for i, (_, step, _) in enumerate(ops)])
        outs = when_all(futs).result()
        # The device wait: from the first thunk's slot read to the last
        # chunk's end (thunks may run concurrently on a parallel executor).
        self._blocked_s += max(b for _, b in waits) - min(a for a, _ in waits)
        self.host_roundtrips += 1
        self._warm_prefill.update(padded for _, _, padded in ops)

        # Cache writes and state transitions happen on the caller's
        # thread, after the join — chunk thunks never mutate the pool.
        prefill_ops, finished = [], []
        for (req, step, padded), (logits, new_row) in zip(ops, outs,
                                                          strict=True):
            with spans.span("serve.prefill.write_slot"):
                if self.paged:
                    # Scatter only the freshly-computed range into the
                    # slot's pages: rows before ``prefilled`` may belong
                    # to a shared (read-only) prefix.
                    self.pool.write_slot(req.slot, new_row, req.prefilled,
                                         req.prefilled + padded)
                else:
                    self.pool.write_slot(req.slot, new_row)
            req.prefilled += step
            self.pool.positions[req.slot] = req.prefilled
            prefill_ops.append((req.rid, step))
            if req.remaining_prefill == 0:
                # First-token sync: the scheduler needs this token on the
                # host to route the request into decode.  Explicit so the
                # strict-mode transfer guard stays armed for the rest.
                with spans.span("serve.prefill.first_token"):
                    tok = int(jax.device_get(  # repro-lint: disable=RL002
                        jnp.argmax(logits[0, 0])))
                req.out.append(tok)
                req.first_token_at = self.clock()
                req.landed_at.append(req.first_token_at)
                req.state = RequestState.DECODE
                if self.paged and req.host_tokens is not None:
                    # Publish the freshly-prefilled prompt's pages into
                    # the prefix cache (refcounted, shared read-only;
                    # the slot's own next write CoW-copies the tail).
                    self.pool.register_prefix(req.slot, req.host_tokens)
                if len(req.out) >= req.max_new_tokens:
                    self._finish(req)
                    finished.append(req.rid)
                elif self._fused:
                    # The host knows this slot's next input token; the
                    # device carry learns it at the next dispatch.
                    self._tok_overrides[req.slot] = tok
                    if self._spec:
                        # Seed the slot's history ring with the prompt
                        # tail + first token: the n-gram proposer drafts
                        # from it at the very first speculative dispatch.
                        seed = list(req.host_tokens or ()) + [tok]
                        self._hist_overrides[req.slot] = \
                            seed[-self.spec_history:]
        return prefill_ops, finished

    # -- decode (per-tick path) ---------------------------------------------
    def _decode_step(self):
        if self._decode_jit is None:
            # The per-lane step is shared with the fused loop
            # (decode_loop.make_lane_step), so the two paths cannot
            # drift numerically — token identity is by construction.
            lanes = make_lane_step(self.cfg, window=self.window,
                                   kernel_tuner=self.kernel_tuner)

            def decode_all(params, caches, toks, poss, active):
                next_toks, new_caches = lanes(params, caches, toks, poss)
                return next_toks, masked_merge(caches, new_caches, active)

            self._decode_jit = jax.jit(decode_all)
        return self._decode_jit

    def _run_decode(self):
        decs = [r for r in self._active if r.state is RequestState.DECODE]
        if not decs:
            return [], []
        n = self.pool.n_slots
        toks = [0] * n
        active = [False] * n
        for r in decs:
            toks[r.slot] = r.out[-1]
            active[r.slot] = True
        step = self._decode_step()
        pool, params = self.pool, self.params
        toks_a = jnp.asarray(toks, jnp.int32)
        poss_a = pool.positions_array()
        active_a = jnp.asarray(active, dtype=bool)

        def run_decode(_):
            # Synchronised for the same reason as the prefill thunks.
            return jax.block_until_ready(
                step(params, pool.caches, toks_a, poss_a, active_a))

        if self._warm_decode:   # cold call compiles; keep it untimed
            tag_workload(run_decode, self.decode_key, elems=len(decs))
        t_dev = time.perf_counter()
        fut = self.executor.then_execute(run_decode, Future.ready(None))
        self._warm_decode = True
        next_toks, new_caches = fut.result()
        self.pool.caches = new_caches
        next_toks = jax.device_get(next_toks)
        self._blocked_s += time.perf_counter() - t_dev
        landed = self.clock()
        self.decode_dispatches += 1
        self.decode_tokens += len(decs)
        self.decode_loop_iters += 1
        self.host_roundtrips += 2   # block_until_ready + device_get

        decoded, finished = [], []
        for r in decs:
            self.pool.positions[r.slot] += 1
            r.out.append(int(next_toks[r.slot]))
            r.landed_at.append(landed)
            decoded.append(r.rid)
            if len(r.out) >= r.max_new_tokens \
                    or self.pool.positions[r.slot] >= self.max_len:
                self._finish(r)
                finished.append(r.rid)
        return decoded, finished

    # -- decode (fused path) -------------------------------------------------
    def _fused_step(self):
        if self._fused_jit is None:
            if self.paged:
                self._fused_jit = make_paged_decode_step(
                    self.cfg, page_size=self.pool.page_size,
                    max_len=self.max_len,
                    kernel_tuner=self.kernel_tuner,
                    max_depth=self.max_dispatch_depth,
                    cache_shardings=self.pool.shardings)
            else:
                self._fused_jit = make_fused_decode_step(
                    self.cfg, window=self.window,
                    kernel_tuner=self.kernel_tuner,
                    max_depth=self.max_dispatch_depth,
                    cache_shardings=self.pool.shardings)
        return self._fused_jit

    def _spec_step(self, depth: int):
        """The compiled speculative fused step for draft window
        ``depth`` (one executable per depth — the verify width is a
        static shape; the tiny dict caches them across the adaptive
        re-decisions)."""
        fn = self._spec_jit.get(depth)
        if fn is None:
            if self.paged:
                fn = make_paged_spec_decode_step(
                    self.cfg, depth=depth,
                    page_size=self.pool.page_size, max_len=self.max_len,
                    history=self.spec_history,
                    kernel_tuner=self.kernel_tuner,
                    max_depth=self.max_dispatch_depth,
                    cache_shardings=self.pool.shardings)
            else:
                fn = make_spec_decode_step(
                    self.cfg, depth=depth, history=self.spec_history,
                    window=self.window, kernel_tuner=self.kernel_tuner,
                    max_depth=self.max_dispatch_depth,
                    cache_shardings=self.pool.shardings)
            self._spec_jit[depth] = fn
        return fn

    def _decode_hist(self) -> jax.Array:
        """The device-resident per-lane token-history ring the n-gram
        proposer drafts from, with any host-known seeds (prompt tails
        captured at prefill completion) spliced in — same dense-where
        splice rationale as ``_decode_toks``."""
        n, h = self.pool.n_slots, self.spec_history
        if self._dev_hist is None:
            self._dev_hist = jnp.full((n, h), -1, jnp.int32)
        if self._hist_overrides:
            mask = [False] * n
            vals = [[-1] * h for _ in range(n)]
            for slot, seed in self._hist_overrides.items():
                mask[slot] = True
                tail = list(seed[-h:])
                vals[slot] = [-1] * (h - len(tail)) + tail
            self._dev_hist = jnp.where(
                jnp.asarray(mask)[:, None],
                jnp.asarray(vals, jnp.int32), self._dev_hist)
            self._hist_overrides.clear()
        return self._dev_hist

    def _decide_spec_depth(self) -> int:
        """Speculation width for the next dispatches — the
        ``serve_spec_depth`` decision.  Fixed widths are traced as such;
        ``auto`` asks the engine to trade expected accepted tokens per
        verify (geometric in the acceptance rate) against the wider
        verify's cost, seeded from the analytic prior acceptance before
        any drain has observed real accept/reject behaviour and refined
        online afterwards (``serve_spec_accept`` EMAs the acceptance
        rate itself, recovered at each drained dispatch's own width —
        see ``_drain``).  Widening is hysteretic (one candidate rung per
        decision); collapsed acceptance forces depth 1 — speculation
        backs off to plain fused decode."""
        cap = min(self.max_spec_depth, self.max_dispatch_depth)
        model = self.decision_model()
        if self.speculate != "auto":
            d = max(min(int(self.speculate), cap), 1)
            if model is not None:
                model.note(self.spec_depth_key, policy="fixed-spec-depth",
                           cores=1, chunk=d, inputs=(("fixed", True),))
            return d
        if model is None:     # static params object: no store to consult
            return min(2, cap)
        with spans.span("serve.decide", kind="spec_depth"):
            evidence = (self.spec_accept_key, self.spec_step_key,
                        self.fused_key)
            inputs: tuple = ()
            ema = model.smoothed_t_iter(self.spec_accept_key)
            if ema is None:
                accept = DEFAULT_SPEC_ACCEPT
                inputs += (("seeded", True),)
            else:
                # The EMA already holds the acceptance rate (recovered at
                # each dispatch's own width in ``_drain``) — no inversion.
                accept = min(max(float(ema), 0.0), 0.999)
                inputs += (("accept_ema", round(float(ema), 4)),)
            step = model.smoothed_t_iter(self.spec_step_key) \
                or model.smoothed_t_iter(self.fused_key) or 0.0
            cands = tuple(c for c in SPEC_DEPTH_CANDIDATES if c <= cap) \
                or (1,)
            # Width cost measured, not assumed: the EMA'd speculative round
            # seconds over the EMA'd width-1 iteration seconds prices the
            # wider verify on *this* host and config (on CPU a width-2 GEMM
            # can even beat the width-1 GEMV — the static prior cannot know
            # that).  Falls back to the analytic prior until both step EMAs
            # hold samples.
            kwargs: dict = {}
            spec_s = model.smoothed_t_iter(self.spec_step_key)
            iter_s = model.smoothed_t_iter(self.fused_iter_key)
            if spec_s and iter_s:
                d_ref = max(self._spec_depth, 2)
                wc = (spec_s / iter_s - 1.0) / (d_ref - 1.0)
                kwargs["width_cost"] = min(max(wc, 0.0), 1.0)
                inputs += (("width_cost_online", True),)
            decision = model.spec_depth(
                self.spec_depth_key, candidates=cands, accept_rate=accept,
                step_s=step, max_depth=cap, current=self._spec_depth,
                evidence=evidence, inputs=inputs, **kwargs)
        return decision.chunk

    def spec_stats(self) -> dict:
        """Cumulative speculation telemetry (benchmarks and the serve
        CLI surface it): verify events, tokens they emitted, loop
        rounds, the tokens-per-verify ratio, the EMA'd acceptance rate
        (per-dispatch-width samples; inverted from tpv only when no
        decision store is attached), and the width itself."""
        tpv = self.spec_emitted / self.spec_verifies \
            if self.spec_verifies else 0.0
        d = self._spec_depth if self._spec else 0
        model = self.decision_model()
        ema = model.smoothed_t_iter(self.spec_accept_key) \
            if model is not None else None
        accept = float(ema) if ema is not None \
            else ((tpv - 1.0) / (d - 1.0) if d >= 2 and tpv else 0.0)
        return {"enabled": self._spec, "depth": d,
                "verifies": self.spec_verifies,
                "emitted": self.spec_emitted,
                "rounds": self.spec_rounds,
                "tokens_per_verify": tpv,
                "acceptance_rate": max(accept, 0.0)}

    def decode_cost_analysis(self) -> dict:
        """Per-device XLA costs of one decode loop iteration: flops,
        HBM bytes accessed, and collective wire bytes (analysis/roofline
        conventions; ``cost_analysis()`` is per-device, and a
        ``fori_loop`` body is counted once — i.e. the numbers are per
        iteration, so achieved TFLOP/s = flops × ``decode_loop_iters`` /
        makespan).  Lowering reuses the already-compiled executable via
        the jit cache; a step that fails to lower or compile raises."""
        from ..analysis import roofline

        n = self.pool.n_slots
        toks = jnp.zeros(n, jnp.int32)
        poss = self.pool.positions_array()
        steps = jnp.zeros(n, jnp.int32)
        pt = (self.pool.page_table_array(),) if self.paged else ()
        if self._fused and self._spec and self._spec_depth >= 2:
            # Speculative hot path: cost the spec step's loop body (one
            # verify round — ``decode_loop_iters`` counts exactly those
            # rounds on this path).
            hist = jnp.full((n, self.spec_history), -1, jnp.int32)
            lowered = self._spec_step(self._spec_depth).lower(
                self.params, self.pool.caches, *pt, hist, toks, poss, steps)
        elif self._fused:
            lowered = self._fused_step().lower(
                self.params, self.pool.caches, *pt, toks, poss, steps)
        else:
            lowered = self._decode_step().lower(
                self.params, self.pool.caches, toks, poss,
                jnp.zeros(n, dtype=bool))
        flops, byts, wire, _ = roofline.extract_costs(lowered.compile())
        return {"flops_per_device": flops,
                "hbm_bytes_per_device": byts,
                "collective_wire_bytes_per_device": wire,
                "n_devices": 1 if self.mesh is None
                else self.mesh.devices.size}

    def _decode_toks(self) -> jax.Array:
        """The device-resident last-token carry, with any host-known
        updates (prefill-emitted first tokens) spliced in.  The splice
        is a dense ``where`` over the (tiny) slot vector — a scatter
        with dynamic indices costs a two-orders-of-magnitude larger
        one-time compile for no win at this size."""
        if self._dev_toks is None:
            self._dev_toks = jnp.zeros(self.pool.n_slots, jnp.int32)
        if self._tok_overrides:
            n = self.pool.n_slots
            mask = [False] * n
            vals = [0] * n
            for slot, tok in self._tok_overrides.items():
                mask[slot] = True
                vals[slot] = tok
            self._dev_toks = jnp.where(jnp.asarray(mask),
                                       jnp.asarray(vals, jnp.int32),
                                       self._dev_toks)
            self._tok_overrides.clear()
        return self._dev_toks

    def _decide_depth(self, decs) -> int:
        """Tokens per dispatch for this tick — the ``serve_dispatch_depth``
        decision.  Fixed depths are traced as such; ``auto`` asks the
        engine to amortise the measured host tick overhead against the
        measured device step time (seeded, before any observation, from
        the calibrated empty-dispatch T0 plus the decision-engine
        microbench's per-query cost — the host work a tick provably
        pays)."""
        model = self.decision_model()
        if self.dispatch_depth != "auto":
            depth = min(self.dispatch_depth, self.max_dispatch_depth)
            if model is not None:
                model.note(self.depth_key, policy="fixed-depth",
                           cores=1, chunk=depth,
                           inputs=(("fixed", True),))
            return depth
        if model is None:     # static params object: no store to consult
            return min(8, self.max_dispatch_depth)
        with spans.span("serve.decide", kind="depth"):
            evidence = [self.host_tick_key, self.fused_key]
            inputs: tuple = ()
            host = model.smoothed_t_iter(self.host_tick_key)
            if host is None:
                t0 = self.acc.calibrate_t0(self.executor)
                host = t0 + 4.0 * decision_overhead_s()
                inputs = (("seeded", True),)
            dev = model.smoothed_t_iter(self.fused_key)
            if dev is None:
                # Fall back to the per-tick decode key's smoothed value, or
                # the analytic roofline profile behind it.
                dev = self.acc.measure_iteration(
                    self.executor, self.decode_profile, max(len(decs), 1),
                    key=self.decode_key)
                evidence.append(self.decode_key)
            decision = model.dispatch_depth(
                self.depth_key, host_overhead_s=host, device_step_s=dev,
                max_depth=self.max_dispatch_depth,
                eff=getattr(self.acc, "efficiency",
                            overhead_law.DEFAULT_EFFICIENCY),
                evidence=tuple(evidence), inputs=inputs)
        return decision.chunk

    def _decide_page_size(self) -> int:
        """Construction-time ``serve_page_size`` decision: the page size
        minimising the Overhead-Law cost of the paged pool —
        per-request page management (measured ``serve_page_mgmt``, paid
        ``max_len / ps`` times) against half a page of wasted prefill
        per prompt tail (priced at the online-refined ``serve_prefill``
        t_iter).  Analytic on a cold store; once this process has
        observed real page-management waves the re-decisions on timed
        syncs carry online provenance, and the refined choice drives
        the next pool built over the same store."""
        model = self.decision_model()
        if model is None:
            return 16
        with spans.span("serve.decide", kind="page_size"):
            mgmt = model.smoothed_t_iter(self.page_mgmt_key) or 0.0
            pf = model.smoothed_t_iter(self.prefill_key)
            inputs: tuple = ()
            if pf is None:
                pf = self.acc.measure_iteration(
                    self.executor, self.prefill_profile, self.max_len,
                    key=self.prefill_key)
                inputs = (("seeded", True),)
            decision = model.page_size(
                self.page_size_key, candidates=DEFAULT_PAGE_CANDIDATES,
                max_len=self.max_len, page_mgmt_s=mgmt,
                prefill_token_s=pf or 0.0,
                evidence=(self.page_mgmt_key, self.prefill_key),
                inputs=inputs)
        return decision.chunk

    def _decide_interleave(self, chunk: int) -> int:
        """Per-tick ``serve_prefill_interleave`` decision: how many
        prefill chunk-ops fit the window the in-flight fused decode
        keeps the device busy.  The window is the online-refined fused
        per-token time × the last dispatch depth × the active decode
        lanes; one chunk costs the online-refined prefill t_iter × the
        decided chunk.  More chunks than fit stall the decode lanes
        (``prefill_stall_s``); fewer starve admission."""
        ready = sum(1 for r in self._active
                    if r.state is RequestState.PREFILL)
        cap = max(ready, 1)
        if self.prefill_interleave != "auto":
            r = min(int(self.prefill_interleave), cap)
            model = self.decision_model()
            if model is not None:
                model.note(self.interleave_key, policy="fixed-interleave",
                           cores=1, chunk=max(r, 1),
                           inputs=(("fixed", True),))
            return max(r, 1)
        model = self.decision_model()
        if model is None:
            return cap
        with spans.span("serve.decide", kind="interleave"):
            n_dec = sum(1 for r in self._active
                        if r.state is RequestState.DECODE)
            dev = model.smoothed_t_iter(self.fused_key) or 0.0
            window = dev * max(self._last_depth, 1) * max(n_dec, 1)
            t_pf = model.smoothed_t_iter(self.prefill_key)
            inputs: tuple = (("depth", self._last_depth), ("lanes", n_dec))
            if t_pf is None:
                t_pf = self.acc.measure_iteration(
                    self.executor, self.prefill_profile, max(chunk, 1),
                    key=self.prefill_key)
                inputs += (("seeded", True),)
            decision = model.prefill_interleave(
                self.interleave_key, pending_chunks=ready,
                decode_window_s=window,
                chunk_cost_s=max(t_pf or 0.0, 0.0) * max(chunk, 1),
                max_chunks=self.pool.n_slots,
                evidence=(self.fused_key, self.prefill_key,
                          self.host_tick_key),
                inputs=inputs)
        return decision.chunk

    def _dispatch_decode(self):
        """Dispatch one fused decode step (no sync): every DECODE slot
        advances by up to the decided depth, clamped to its remaining
        token budget and cache room, with finish bookkeeping done
        immediately — the tokens themselves land later via ``_drain``."""
        decs = [r for r in self._active if r.state is RequestState.DECODE]
        if not decs:
            return [], [], 0, 0, 0
        with spans.span("serve.dispatch") as sp:
            return self._dispatch(decs, sp)

    def _dispatch(self, decs: list, sp) -> tuple:
        """``_dispatch_decode`` inside its ``serve.dispatch`` span ``sp``,
        which gets the dispatch's lane counts (``_count_lanes``)."""
        depth = self._decide_depth(decs)
        self._last_depth = depth
        spec_d = self._spec_depth if self._spec else 1
        if self._spec and self.speculate == "auto" and spec_d < 2 \
                and min(self.max_spec_depth, self.max_dispatch_depth) >= 2 \
                and self.decode_dispatches % SPEC_PROBE_EVERY == 0:
            # Exploration probe (see SPEC_PROBE_EVERY): depth 1 must not
            # be absorbing, so one window per probe period runs at width
            # 2 to keep the acceptance EMA live.
            spec_d = 2
        use_spec = spec_d >= 2
        # Under speculation every verify window is ``spec_d`` wide
        # regardless of the lane's remaining budget, so the last
        # ``spec_d - 1`` cache positions are reserved: a window must
        # never clamp its KV write over live earlier entries.  The
        # usable cache length is effectively max_len - (spec_d - 1).
        margin = spec_d - 1 if use_spec else 0
        steps = [0] * self.pool.n_slots
        lanes = []
        for r in decs:
            budget = min(r.max_new_tokens - len(r.out) - r.pending_out,
                         self.max_len - margin
                         - self.pool.positions[r.slot])
            take = max(min(depth, budget), 0)
            steps[r.slot] = take
            lanes.append((r, r.slot, take))
        if self.paged:
            # CoW/allocation must land before the dispatch reads the
            # pool, and the table upload after — the loop body's gather
            # indirection is exactly this tick's host-resolved mapping.
            # Speculation widens the writable span by the draft margin:
            # rejected drafts scatter into positions past the accepted
            # frontier, and those writes must only ever land in pages
            # this slot owns exclusively (kv_cache.rollback enforces
            # the refcount invariant).
            t_pg = time.perf_counter()
            for _, slot, take in lanes:
                if take:
                    pos = self.pool.positions[slot]
                    self.pool.ensure_writable(
                        slot, pos, min(pos + take + margin, self.max_len))
            model = self.decision_model()
            if model is not None:
                model.observe(self.page_mgmt_key, len(lanes),
                              max(time.perf_counter() - t_pg, 0.0))
        toks_a = self._decode_toks()
        poss_a = self.pool.positions_array()
        steps_a = jnp.asarray(steps, jnp.int32)
        step_id = ("spec", spec_d) if use_spec else "fused"
        fused = self._spec_step(spec_d) if use_spec else self._fused_step()
        # Periodic synced dispatch: the only way to wall-clock the
        # device step honestly is with an empty pipeline around it.
        timed = step_id in self._warm_steps and \
            self.decode_dispatches % self.sync_every == 0
        if timed:
            self._drain(drop_to=0)
        with spans.span("serve.dispatch.launch", synced=timed) as launch:
            pt = (self.pool.page_table_array(),) if self.paged else ()
            stats = None
            if use_spec:
                new_caches, new_hist, out_buf, final_toks, stats = fused(
                    self.params, self.pool.caches, *pt,
                    self._decode_hist(), toks_a, poss_a, steps_a)
            else:
                new_hist = None
                new_caches, out_buf, final_toks = fused(
                    self.params, self.pool.caches, *pt, toks_a, poss_a,
                    steps_a)
            self.pool.mark_donated("fused decode dispatch")
            if timed:
                # The periodic honest-timing sync (one per
                # ``sync_every`` dispatches) — budgeted by design, see
                # class docstring.
                jax.block_until_ready(out_buf)  # repro-lint: disable=RL002
        total = sum(take for _, _, take in lanes)
        if timed:
            dt = launch.end - launch.start
            self._blocked_s += dt
            self.host_roundtrips += 1
            model = self.decision_model()
            if model is not None and total > 0:
                model.observe(self.fused_key, total, dt)
                if stats is not None:
                    # Pipeline is empty and the buffer ready: reading
                    # the loop-round count here is the same sanctioned
                    # sync, and it prices one speculative verify round
                    # for the depth decision.
                    rounds = int(jax.device_get(  # repro-lint: disable=RL002
                        stats)[0])
                    if rounds > 0:
                        model.observe(self.spec_step_key, rounds, dt)
                else:
                    # Width-1 per-iteration cost, uncontaminated by
                    # speculation — the denominator of the online
                    # width_cost (see _decide_spec_depth).
                    iters = max((take for _, _, take in lanes),
                                default=0)
                    if iters > 0:
                        model.observe(self.fused_iter_key, iters, dt)
            if self.paged and self._page_size_auto:
                # Re-decide with whatever page-management and prefill
                # costs the run has observed by now: the trace shows the
                # layout decision upgrading analytic → online, and the
                # refined size drives the next pool over this store
                # (geometry is compiled in — it cannot change mid-run).
                self._decide_page_size()
            if self._spec and self.speculate == "auto":
                # Re-decide the speculation width with the acceptance
                # rate the drains have observed — analytic → online in
                # the trace, with backoff to 1 when acceptance collapses.
                self._spec_depth = self._decide_spec_depth()
        self._warm_fused = True
        self._warm_steps.add(step_id)
        self.pool.adopt(new_caches)
        self._dev_toks = final_toks
        if new_hist is not None:
            self._dev_hist = new_hist
        self.decode_dispatches += 1
        self.decode_tokens += total
        # What every other slot holds while this dispatch's loop runs:
        # a request in prefill, or nothing (with requests waiting or not).
        others = (sum(1 for r in self._active
                      if r.state is RequestState.PREFILL),
                  self.pool.free_slots(), bool(self._waiting))
        sp.attrs.update(depth=depth, tokens=total)
        if stats is None:
            iters = max((take for _, _, take in lanes), default=0)
            self.decode_loop_iters += iters
            self._count_lanes(sp, iters, total, len(lanes), *others)
        # else: speculative loop rounds are variable — counted at drain
        # time from the dispatch's stats vector, lanes with them.
        self._inflight.append(
            (out_buf, stats, spec_d if use_spec else 0, lanes, others))

        decoded, finished = [], []
        for r, slot, take in lanes:
            self.pool.advance(slot, take)
            r.pending_out += take
            decoded.append(r.rid)
            if len(r.out) + r.pending_out >= r.max_new_tokens \
                    or self.pool.positions[slot] >= self.max_len - margin:
                self._finish(r)
                finished.append(r.rid)
        return decoded, finished, depth, total, spec_d if use_spec else 0

    def _count_lanes(self, sp, iters: int, decode: int, n_lanes: int,
                     n_prefill: int, n_free: int, queued: bool) -> None:
        """Count one dispatch's ``iters`` loop iterations for every slot
        under one of ``LANES``, into ``lane_iters`` and onto the span
        ``sp``.  ``decode`` of the ``iters x n_lanes`` decoding
        lane-iterations produced a token; the rest ran clamped.  Under
        speculation an iteration is one verify round, and ``decode`` the
        lanes' verify events (each emits one to ``d`` tokens)."""
        counts = (decode, iters * n_lanes - decode, iters * n_prefill,
                  iters * n_free if queued else 0,
                  0 if queued else iters * n_free)
        sp.attrs["iters"] = sp.attrs.get("iters", 0) + iters
        for kind, attr, n in zip(LANES, _LANE_ATTRS, counts, strict=True):
            self.lane_iters[kind] += n
            sp.attrs[attr] = sp.attrs.get(attr, 0) + n

    def _drain(self, drop_to: int | None = None,
               harvest: bool = False) -> None:
        """Land emitted tokens from finished fused dispatches.

        ``drop_to=N`` blocks until at most ``N`` dispatches remain in
        flight (the pipeline bound); ``harvest`` additionally pops any
        buffer that is already materialised, without blocking.  One
        ``device_get`` per dispatch — the fused path's only routine
        host round-trip.  The dispatches landed, and whether landing
        them blocked, ride on the call's ``serve.drain`` span; when it
        blocked, the span is device wait (``_blocked_s``)."""
        if not self._inflight:
            return
        with spans.span("serve.drain") as sp:
            landed = self._fetch(drop_to, harvest)
            sp.attrs["landed"] = len(landed)
            sp.attrs["blocked"] = blocked = any(must for *_, must in landed)
            for entry, fetched, at, _ in landed:
                self._land(sp, entry, fetched, at)
        if blocked:
            self._blocked_s += sp.end - sp.start

    def _fetch(self, drop_to: int | None, harvest: bool) -> list:
        """Pop and fetch the dispatches ``_drain`` lands: ``(entry,
        host buffers, landing time, whether it had to wait)`` each."""
        landed = []
        while self._inflight:
            must = drop_to is not None and len(self._inflight) > drop_to
            if not must:
                if not harvest:
                    break
                probe = getattr(self._inflight[0][0], "is_ready", None)
                if probe is not None and not probe():
                    break
            entry = self._inflight.popleft()
            out_buf, stats = entry[0], entry[1]
            # The fused path's one sanctioned round-trip (docstring above).
            if stats is not None:
                fetched = jax.device_get(  # repro-lint: disable=RL002
                    (out_buf, stats))
            else:
                fetched = (jax.device_get(  # repro-lint: disable=RL002
                    out_buf), None)
            landed.append((entry, fetched, self.clock(), must))
            self.host_roundtrips += 1
        return landed

    def _land(self, sp, entry: tuple, fetched: tuple, at: float) -> None:
        """Land one fetched dispatch: its tokens (stamped ``at``, when
        they reached the host) and, under speculation, its telemetry
        and lane counts (on the drain's span ``sp``)."""
        _, _, disp_spec_d, lanes, others = entry
        toks, st = fetched
        if st is not None:
            # Speculation telemetry: loop rounds actually run,
            # per-lane verify events, and tokens they emitted.  The
            # acceptance rate is recovered *here*, at the width this
            # dispatch actually ran (``disp_spec_d``), not later at
            # whatever width the scheduler has since moved to —
            # tokens-per-verify saturates at the dispatch width, so
            # inverting it at any other width mis-reads acceptance.
            # Stored as elems=verifies, seconds=accept × verifies so
            # the EMA's per-element ratio *is* the acceptance rate.
            rounds, verifies, emitted = (int(x) for x in st)
            self.decode_loop_iters += rounds
            self.spec_rounds += rounds
            self.spec_verifies += verifies
            self.spec_emitted += emitted
            self._count_lanes(sp, rounds, verifies, len(lanes), *others)
            model = self.decision_model()
            if model is not None and verifies > 0 \
                    and disp_spec_d >= 2:
                a_s = (emitted / verifies - 1.0) / (disp_spec_d - 1.0)
                # Floor keeps the sample visible to the EMA (the
                # refiner drops zero-cost observations) while
                # staying far below the backoff threshold.
                a_s = min(max(a_s, 1e-3), 0.999)
                model.observe(self.spec_accept_key, verifies,
                              a_s * verifies)
        for req, slot, take in lanes:
            req.pending_out -= take
            if req.state is RequestState.CANCELLED:
                # Dispatched before the cancel landed: the buffer is
                # drained (the slot bookkeeping must balance) but
                # the tokens are dropped, never emitted.
                continue
            req.out.extend(
                # ``toks`` is host numpy already — not a device sync.
                int(toks[j, slot])  # repro-lint: disable=RL002
                for j in range(take))
            req.landed_at.extend([at] * take)
            if req.state is RequestState.DONE \
                    and req.pending_out <= 0 \
                    and req.finished_at is None:
                self._truncate(req)
                self._stamp_finished(req)

    def flush(self) -> None:
        """Block until every in-flight fused dispatch has drained."""
        self._drain(drop_to=0)

    def _finish(self, req: Request) -> None:
        req.state = RequestState.DONE
        self.pool.release(req.slot)
        if req.pending_out <= 0:
            self._truncate(req)
            self._stamp_finished(req)
        # else: the drain that lands the final tokens truncates at the
        # stop point and stamps finished_at (serve/decode_loop.py).

    @staticmethod
    def _truncate(req: Request) -> None:
        """Cut the output (and its landing stamps) at the stop point."""
        req.out = req.out[:req.max_new_tokens]
        req.landed_at = req.landed_at[:req.max_new_tokens]

    def _stamp_finished(self, req: Request) -> None:
        """Stamp completion time and charge a deadline miss if the
        request's tokens landed past its deadline (SLO accounting: a
        late completion is wasted work, same as a shed)."""
        req.finished_at = self.clock()
        self._record_request(req)
        if req.deadline is not None and req.finished_at > req.deadline:
            self.deadline_misses += 1
            self._tick_misses += 1

    @staticmethod
    def _record_request(req: Request) -> None:
        """The request's ``serve.request`` span, written once it is
        terminal: submitted (``arrival``) to finished, with its
        admission and first-token stamps (scheduler clock)."""
        spans.record("serve.request", req.arrival, req.finished_at,
                     rid=req.rid, state=req.state.value,
                     submitted=req.arrival, admitted=req.admitted_at,
                     first_token=req.first_token_at,
                     finished=req.finished_at, tokens=len(req.out))
