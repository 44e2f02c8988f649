"""The DEIS sampling service: :class:`DiffusionServeEngine` runs the paper's
workload as a *streaming continuous-batching* service over the pure
``step()`` executor.

Diffusion serving semantics
---------------------------

Admission.  ``submit()`` enqueues; at every scheduler ``tick()`` pending
requests are admitted into *groups* at a step boundary. A group stacks up to
``max_group`` requests whose plans share one :attr:`SolverPlan.family` and
whose (bucketed) ``seq_len`` matches -- solver *names* may differ (ddim /
euler / naive_ei stack into a single solve via
:func:`repro.core.plan.stack_plans`) and so may NFE budgets: shorter plans
are padded to the bucket's longest grid with
:func:`repro.core.plan.pad_plan` (*ragged* groups). Each request gets its
own PRNG key derived from its own ``Request.seed``, so samples are
per-request reproducible regardless of batch composition, admission time,
joining, or compaction.

Admission is *continuous*: at every step boundary pending requests
**join** an in-flight group of their bucket and priority that has room
(``max_group`` minus its live rows; retired rows and structural filler are
slots too) instead of waiting for a fresh one -- so a request that arrives
while another of its bucket is in flight shares that group's row tiles
rather than running a half-empty tile of its own (a tile is
:func:`repro.diffusion.lm.tile_rows` of the bucket's length wide: 8 rows at
seq 32, 2 at seq 128; ``serve_eps_tiles_total`` and
``serve_eps_tile_rows_total`` count the tiles run and their row slots).
Joiner plan rows are
padded to the group's grid and spliced onto the in-flight rows, which stay
bitwise unmoved, and the executor steps every row at its OWN count (a
per-row ``k`` vector: joiners start at 0 while veterans continue), so a
warm ragged workload converges to a small fixed set of ``(family, batch,
seq_len)`` executors that never drain and never recompile. A joiner whose
grid exceeds the group's horizon forms a fresh group instead (extending the
grid would change the signature).
``seq_len_buckets=(...)`` additionally rounds request lengths up to bucket
edges (the solve carries the tail as extra latent positions; every emitted
decode is masked back to the request's true ``seq_len``), so e.g. seq 48
and 64 share one executor cache entry.

Scheduling.  A tick selects up to ``steps_per_tick`` groups (default: all)
and advances each by ONE solver step, so a newly admitted 5-NFE request
starts making progress immediately instead of waiting behind a 50-NFE group.
Selection is priority/deadline-aware, not round-robin: groups are ordered by
effective priority (max member ``Request.priority``, boosted by one level
per ``aging_ticks`` consecutive skipped ticks -- starvation aging), then
earliest absolute deadline (min member ``submit time + deadline_s``; no
deadline sorts last), then admission order. With the default
``steps_per_tick=None`` every active group steps each tick and the ordering
only decides dispatch order; a throttled driver (``steps_per_tick=k``) gets
true earliest-deadline-first with guaranteed progress for starved work.

Completion, compaction & refill.  Rows of a ragged group finish at their
OWN step count (``g.k - k0 == n_steps``; a joiner's ``k0`` is its admission
tick): a finished row's Result is emitted from that very tick (its latency
is the group's solve time accumulated since ITS admission), not when the
whole group drains. The group rebuilds at the next tick's admission
boundary, before it steps again: freed rows are refilled with pending
joiners, or the survivors are row-gathered
(:func:`repro.core.plan.take_rows` +
:func:`repro.core.sampler.take_state_rows`) into a smaller
``(signature, batch, seq_len)`` bucket and keep stepping there -- a
compaction is a join with no joiners. Both moves preserve bitwise
per-request reproducibility because every per-row quantity --
coefficients, iterate, eps history, PRNG key chain -- moves whole. So no
retired request row ever steps: ``wasted_row_steps`` counts such steps
and stays zero (structural filler excluded).

Compile cache.  One jitted ``step`` is AOT-compiled per
``(plan.signature, batch, seq_len)`` and reused across groups, solver names
and step indices (``k`` is traced as a PER-ROW vector, so the same
executable serves uniform groups and post-join groups whose rows run at
their own counts; pndm's warmup/tail split is a ``lax.cond``). Compaction
looks its smaller batch up in the same cache, so a steady-state workload
runs with ZERO recompilation. The boundary pass's row moves -- the
splice of joiners, the gather of survivors, the pick of finished rows to
decode -- run the jitted device halves of ``join_rows`` / ``take_rows``
and their state twins, one program per (rows in, rows out), compiled
beside the executor of the batch they end at, so a warm-up that builds
the executors has built them too (unsharded; under a mesh they compile on
first use).
``Result.compile_s`` carries the trace+compile cost charged to the group
that needed the executor; ``Result.latency_s`` is pure solve wall-time, so
benchmark numbers are not poisoned by trace cost.

Callback contract.  ``serve(..., on_step=fn)`` invokes ``fn(StepEvent)``
after every group step with the group's uids and progress; with
``stream_decode=True`` the event also carries the partial decode of the
current iterate (streamed tokens). ``StepEvent.row_steps`` gives each
request's own total step count so per-request progress is well-defined in a
ragged group. The callback runs on the scheduler thread between steps --
keep it cheap or copy the event out (the async ``ServeDriver`` fans it out
to per-request streams).

Each NFE is one full-sequence backbone forward, so this is where DEIS's
small-NFE advantage becomes throughput: serving capacity scales ~1/NFE.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import math
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core import cached_make_plan, get_timesteps
from ..core import sampler as SAMPLER
from ..core.adaptive import RetirePolicy
from ..core.plan import (SolverPlan, concat_plan_rows, gather_plan_rows,
                         inert_row, join_rows, pad_plan, solver_stages,
                         stack_plans, take_rows)
from ..core.sde import SDE, VPSDE
from ..diffusion import lm as DLM
from ..obs.metrics import MetricsRegistry
from ..obs.trace import Tracer


class DeadlineExceeded(RuntimeError):
    """A request's absolute deadline passed before its solve finished.

    With ``enforce_deadlines=True`` the engine evicts the row at the next
    boundary pass and emits a :class:`Result` flagged
    ``deadline_exceeded=True`` (empty tokens, true queue wait, the solve
    time spent so far). The driver converts that flag into THIS exception
    on the request's own stream -- the scheduler thread never raises it, so
    a deadline storm can degrade individual requests but never the service.
    """


class Cancelled(RuntimeError):
    """A request was cancelled (``engine.cancel`` / ``driver.cancel``).

    The engine retires the row through the same boundary machinery as a
    deadline eviction (the freed slot is recycled via join/compaction) and
    emits a :class:`Result` flagged ``cancelled=True`` (empty tokens, the
    solve time burned so far). The driver converts that flag into THIS
    exception on the request's own stream.
    """


@dataclasses.dataclass
class Request:
    """One sampling request.

    ``priority`` (higher = more urgent) and ``deadline_s`` (latency budget in
    seconds, relative to submit time; ``None`` = best-effort) feed the
    engine's priority/deadline-aware scheduler. They influence WHEN a
    request is stepped, never WHAT it computes: samples depend only on
    ``(solver, nfe, eta, seed, seq_len)``.
    """
    uid: int
    seq_len: int = 64                      # sample length
    nfe: int = 10
    solver: str = "tab3"
    eta: float | None = None               # required iff solver == "ddim_eta"
    seed: int = 0
    priority: int = 0                      # scheduling weight (higher first)
    deadline_s: float | None = None        # latency budget from submit time


@dataclasses.dataclass
class Result:
    """Final per-request outcome. ``latency_s`` is the request's group solve
    time accumulated from ITS OWN admission tick (a joiner is not charged
    the group's pre-join solve time) up to the tick its row finished (ragged
    rows finish early); ``nfe`` is the true evals its own plan spent (never
    the padded group's); ``compile_s`` is trace+compile charged to its
    group; ``queue_wait_s`` is the time the request spent pending before
    entering a group (fresh admission or join)."""
    uid: int
    tokens: np.ndarray
    latency_s: float            # solve wall-time of the request's group
                                # since ITS admission, EXCLUDING
                                # compile/trace (see compile_s)
    nfe: int = 0                # true network evals spent (plan.nfe)
    compile_s: float = 0.0      # trace+compile charged to this group's
                                # executor; 0.0 on a warm compile cache
    queue_wait_s: float = 0.0   # submit -> admission (join or fresh group)
    deadline_exceeded: bool = False  # evicted by deadline enforcement:
                                     # tokens is empty, nfe is 0 (no sample
                                     # was produced), latency_s is the solve
                                     # time burned before eviction
    cancelled: bool = False     # retired by cancel(): tokens empty, nfe 0
    early_exit: bool = False    # retired early by the engine's RetirePolicy:
                                # tokens IS a converged sample; nfe is the
                                # evals actually spent (< the request's
                                # budget; the difference is the saved NFEs)
    final_err: float | None = None  # last local-error estimate of the row
                                    # (None when its plan carries no
                                    # embedded pair or no estimate exists)
    moe_assignments: int | None = None  # token-expert pairs the held
                                    # experts of a dropless MoE computed for
                                    # this request: its valid positions,
                                    # every layer, every NFE (None: no such
                                    # experts, or a pndm plan, whose steps
                                    # run their evals under a cond)


@dataclasses.dataclass
class StepEvent:
    """Per-step progress emitted to the ``on_step`` serving callback.

    In a ragged group ``n_steps`` is the group's drain horizon (the longest
    live ``admission step + own step count``); ``row_steps[i]`` is request
    ``uids[i]``'s own total and ``row_k[i]`` its own completed count (a
    joiner's count starts at its admission tick, not group birth), so
    per-request progress is ``min(row_k[i], row_steps[i]) / row_steps[i]``
    (this is what the driver reports on each request's stream).
    """
    uids: tuple                      # requests in the group that just stepped
    k: int                           # group steps completed (1-based after
                                     # the step; joiners admit at k > 0)
    n_steps: int                     # total group steps to drain
    tokens: Optional[np.ndarray] = None  # (R, seq_len) partial decode when
                                         # serve(stream_decode=True); rows at
                                         # the group's BUCKETED seq_len
    row_steps: Optional[tuple] = None    # per-request true step counts
                                         # (aligned with uids)
    row_k: Optional[tuple] = None        # per-request completed step counts
                                         # (aligned with uids)
    row_seq_lens: Optional[tuple] = None  # per-request TRUE seq_lens (for
                                          # slicing bucketed decodes)
    row_err: Optional[tuple] = None  # per-request local-error estimates
                                     # (aligned with uids; None unless the
                                     # group's plans carry embedded pairs --
                                     # entries are +inf until a row's first
                                     # genuine estimate)


# err histogram edges: local-error estimates are small dimensionless
# magnitudes (x-space Linf), nothing like the registry's latency defaults
_ERR_EDGES = (1e-8, 1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0)


def _spent_nfe(method: str, row: "_Row", k_own: int) -> int:
    """Network evals a row has actually spent after ``k_own`` of its OWN
    steps (early exit charges what was used, not the budget). Mirrors the
    grid sizing above: rk pays its stage count per step, pndm pays 3 extra
    evals on each of its 3 warmup steps, everything else is 1:1."""
    if method == "rk":
        return k_own * max(1, row.nfe // max(1, row.n_steps))
    if method == "pndm":
        return k_own + 3 * min(k_own, 3)
    return k_own


# The request's NFE *budget* is honored by sizing the grid as
# max(1, nfe // solver_stages(name)) instead of burning n_steps * stages
# evals (a Request(nfe=10, solver="rho_rk4") used to cost 40 evals). pndm
# spends 3 extra evals on each of its 3 warmup steps, so its grid is nfe - 9
# intervals (floored at the 4 steps PNDM requires).
_PNDM_WARMUP_EXTRA = 9


@dataclasses.dataclass
class _Pending:
    """A submitted request waiting for admission (fresh group or join)."""
    req: Request
    plan: SolverPlan            # unstacked, at the request's own grid
    t_sub: float                # perf_counter at submit (deadline anchor)
    s_len: int                  # BUCKETED seq_len the solve runs at


@dataclasses.dataclass
class _Row:
    """Per-request bookkeeping inside a (possibly ragged) group.

    ``pad`` rows are structural filler, not requests: sharded admission
    rounds group sizes up to a multiple of the mesh's data-axis size with
    inert rows (``req is None``), and sharded compaction/joining may retain
    a retired request's row as filler (``req`` kept, ``pad`` flipped). Pad
    rows never emit Results, never appear in StepEvents, and never count as
    wasted steps -- they exist so the stacked axis always places evenly.

    ``k0`` is the group step count at this row's admission: a joiner starts
    solving at group step ``k0`` and its own step count is ``g.k - k0`` --
    completion, progress, NFE and latency accounting all run on that own
    count, never on the group's age.
    """
    req: Request | None
    n_steps: int                # TRUE solver steps of this request's own plan
    nfe: int                    # TRUE network evals (plan.nfe, pre-padding)
    deadline: float             # absolute deadline (inf when best-effort)
    done: bool = False          # Result already emitted
    pad: bool = False           # structural filler row (see class docstring)
    k0: int = 0                 # group step count at this row's admission
    solve_s0: float = 0.0       # group solve_s at this row's admission
    wait_s: float = 0.0         # submit -> admission queue wait
    moe: int = 0                # held-expert assignments computed so far


@dataclasses.dataclass
class _Group:
    """One in-flight stacked solve (requests admitted together or joined).

    ``rows`` shrinks under compaction and refills under joining; ``k``
    keeps counting from group birth (row completion is
    ``g.k - row.k0 == row.n_steps``).
    """
    rows: list                  # list[_Row], aligned with the stacked axis
    sig: tuple                  # member plans' (padded, unstacked) signature
    bucket: tuple               # admission bucket key (plan.family, s_len)
    seq_len: int                # bucketed seq_len the stacked solve runs at
    plan: SolverPlan            # stacked: leading request axis on all leaves
    state: SAMPLER.SamplerState
    fn: Callable                # AOT-compiled step(params, plan, k, state)
    n_steps: int                # max live row k0 + n_steps (drain horizon)
    compile_s: float            # 0.0 when the executor cache was warm
    priority: int               # max member Request.priority
    deadline: float             # min member absolute deadline (inf if none)
    arrival: int                # admission sequence number (tie-break)
    k: int = 0                  # steps completed
    solve_s: float = 0.0        # accumulated solve wall-time (excl. compile)
    skipped: int = 0            # consecutive ticks not selected (aging)

    @property
    def real_idx(self) -> list:
        """Stacked-axis indices of real (non-filler) rows."""
        return [i for i, r in enumerate(self.rows) if not r.pad]

    @property
    def uids(self) -> tuple:
        return tuple(self.rows[i].req.uid for i in self.real_idx)


class DiffusionServeEngine:
    """Streaming continuous-batching DEIS sampling service.

    See the module docstring for the admission / scheduling / compile-cache /
    callback contracts. ``serve`` drains a request list to completion;
    ``submit`` + ``tick`` expose the scheduler directly so callers (and
    tests) can admit requests while other groups are mid-solve.
    """

    def __init__(self, params, cfg: ModelConfig, sde: Optional[SDE] = None,
                 schedule: str = "quadratic", max_group: int = 8,
                 steps_per_tick: int | None = None, aging_ticks: int = 8,
                 seq_len_buckets=None, mesh=None,
                 enforce_deadlines: bool = False,
                 retire: RetirePolicy | None = None,
                 metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        """``steps_per_tick``: groups advanced per tick (None = all active;
        an int enables true EDF selection). ``aging_ticks``: skipped ticks
        per +1 effective-priority boost (starvation aging).

        Every ``ab``-method plan steps through the fused Pallas megakernel
        (psi/C combination + noise add + error-pair estimate in ONE kernel:
        one HBM round-trip instead of r+3). Stacked fused rows are bitwise
        identical to solo fused rows (the row-block grid axis computes each
        row's blocks independently).

        ``seq_len_buckets``: ascending edge lengths; a request's seq_len
        rounds UP to the first edge that fits, the solve runs at the bucket
        length (tail positions ride as extra latent positions and are
        masked out of every emitted decode), and requests longer than the
        last edge run at their exact length. Bucketing trades a little
        compute on tail positions for executor reuse: seq 48 and 64 under
        a 64 edge share one (signature, batch, 64) compile-cache entry.
        Row content is bucket-independent for deterministic solvers: the
        prior is drawn at the request's TRUE length (zero-padded to the
        bucket) and a per-row ``lens`` vector masks padded tail keys out
        of every attention call, so the valid positions never see the
        tail. (Stochastic per-step solve noise is still drawn at bucket
        shape, so those rows keep a bucket-shape dependence. A dropless
        MoE routes each position on its own hidden state, so tail
        positions never touch valid rows; the capacity-dispatch MoE of
        the training substrate shares its capacity with them.)

        ``mesh``: a ``jax.sharding.Mesh`` with a data-like axis (e.g.
        :func:`repro.launch.mesh.make_request_mesh`) shards every stacked
        solve over the REQUEST axis: params replicate, state/plan request
        leaves get ``NamedSharding`` placements, executors jit with explicit
        in/out shardings, and admission rounds group sizes up to a multiple
        of the data-axis size with inert filler rows so groups always place
        evenly. Sharding changes WHERE rows compute, never what: samples
        stay bitwise identical to the single-device path.

        ``enforce_deadlines``: deadlines stop being advisory. At every
        boundary pass, pending requests AND mid-flight rows whose absolute
        deadline (``submit time + deadline_s``) has passed are evicted: a
        :class:`Result` flagged ``deadline_exceeded=True`` (empty tokens)
        is emitted on the request's own stream, the freed row is recycled
        through the existing join/compaction path, and the eviction is
        counted in ``serve_deadline_evicted_total``. Off by default --
        deadlines then only order the queue (the pre-enforcement behavior),
        so latency-budget hints can never change what a request returns.

        ``retire``: a :class:`~repro.core.adaptive.RetirePolicy` enables
        adaptive early exit. Every plan is built with
        ``error_estimate=True`` (families with an embedded lower-order pair
        maintain a per-row local-error estimate in ``SamplerState.err`` at
        zero extra NFE; the rest never retire early), and the boundary pass
        retires converged rows -- estimate within the policy's tolerance
        after at least ``min_k`` own steps -- through the SAME ``take_rows``
        path as deadline eviction, emitting a Result flagged
        ``early_exit=True`` with the evals actually spent. The decision is a
        pure per-row function of the row's own (estimate, step count,
        magnitude), so the bitwise-reproducibility invariant holds in
        controller form: a solo solve under the IDENTICAL policy retires at
        the identical step with the identical sample. Under load, saved
        NFEs are throughput -- a row finishing at k=7 instead of 10 frees a
        slot a joiner fills the same boundary.

        ``metrics``: a :class:`~repro.obs.metrics.MetricsRegistry` to
        register the engine's counters/gauges/histograms in (share one per
        process to aggregate engines); ``None`` creates a private registry
        at ``engine.metrics``. ``tracer``: a
        :class:`~repro.obs.trace.Tracer` for host-side span timing of
        ticks/steps/compiles/boundary work; ``None`` builds one over the
        same registry. Instrumentation is host-side only -- nothing here
        syncs the device or touches the jitted step."""
        assert cfg.objective == "diffusion"
        self.params, self.cfg = params, cfg
        self.sde = sde or VPSDE()
        self.schedule = schedule
        self.max_group = max_group
        # clamp: 0/negative would make tick() select nothing and busy-loop
        self.steps_per_tick = None if steps_per_tick is None \
            else max(1, steps_per_tick)
        self.aging_ticks = max(1, aging_ticks)
        if seq_len_buckets is not None:
            edges = tuple(int(e) for e in seq_len_buckets)
            if not edges or any(e < 1 for e in edges) or \
                    list(edges) != sorted(set(edges)):
                raise ValueError("seq_len_buckets must be strictly ascending "
                                 f"positive edges, got {seq_len_buckets!r}")
            seq_len_buckets = edges
        self.seq_len_buckets = seq_len_buckets
        self.mesh = mesh
        if mesh is not None:
            from ..launch.mesh import mesh_fingerprint
            from ..sharding.rules import batch_axes
            self._mesh_key = mesh_fingerprint(mesh)
            self._data_size = int(np.prod(
                [mesh.shape[a] for a in batch_axes(mesh)])) or 1
            if self._data_size > self.max_group:
                raise ValueError(
                    f"mesh data-axis size {self._data_size} exceeds "
                    f"max_group={self.max_group}: every group must round up "
                    "to a multiple of the axis, so the smallest placeable "
                    "group would already break the max_group bound. Raise "
                    "max_group or shrink the mesh.")
            # quantize the chunk size so rounded-up groups NEVER exceed the
            # operator's max_group bound (e.g. max_group=10 on an 8-way axis
            # admits 8-request chunks, not 10 -> 16)
            self._chunk_cap = (self.max_group // self._data_size) \
                * self._data_size
            # replicate params over the mesh ONCE; executors AND decode take
            # them as-placed so no per-call transfer happens, and the
            # engine's own reference is the replicated copy (keeping the
            # caller's single-device original alive too would double param
            # memory on device 0)
            self._params_exec = jax.device_put(
                params, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
            self.params = self._params_exec
        else:
            self._mesh_key = None
            self._data_size = 1
            self._chunk_cap = self.max_group
            self._params_exec = params
        self._plans: dict = {}      # (solver, nfe, eta) -> SolverPlan
        self._compiled: dict = {}   # (signature, batch, seq_len, mesh_key)
                                    #   -> AOT step
        self._pending: deque = deque()   # deque[_Pending]
        self._active: list[_Group] = []
        self._arrivals = 0          # admission sequence counter
        self.enforce_deadlines = enforce_deadlines
        self.retire = retire
        # Results produced OUTSIDE a group step (deadline evictions,
        # cancellations, early exits) -- drained into the next tick's
        # finished list
        self._boundary_results: list[Result] = []

        # ---- observability: every scheduler metric lives in the registry
        # (ticks/wasted_row_steps/joined_requests are read-only views over
        # it). Metric objects are resolved ONCE here -- the tick loop
        # touches attributes, never the registry.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(self.metrics)
        reg = self.metrics
        self._m_ticks = reg.counter(
            "serve_ticks_total", "scheduler ticks executed")
        self._m_wasted = reg.counter(
            "serve_wasted_row_steps_total",
            "steps run on already-finished request rows: the boundary "
            "rebuild keeps this at 0")
        self._m_joined = reg.counter(
            "serve_joined_requests_total",
            "requests admitted by joining an in-flight group")
        self._m_submitted = reg.counter(
            "serve_submitted_total", "requests accepted by submit()")
        self._m_completed = reg.counter(
            "serve_completed_total", "requests finished with a sample")
        self._m_evicted = reg.counter(
            "serve_deadline_evicted_total",
            "requests evicted by deadline enforcement")
        self._m_compactions = reg.counter(
            "serve_compactions_total", "mid-flight group compactions")
        self._m_cache_hits = reg.counter(
            "serve_compile_cache_hits_total",
            "executor lookups served by the AOT compile cache")
        self._m_cache_misses = reg.counter(
            "serve_compile_cache_misses_total",
            "executor lookups that traced+compiled a new executable")
        self._m_compile_s = reg.counter(
            "serve_compile_seconds_total",
            "cumulative AOT trace+compile wall time")
        self._g_queue = reg.gauge(
            "serve_queue_depth", "requests pending admission")
        self._g_groups = reg.gauge(
            "serve_active_groups", "stacked groups in flight")
        self._g_occupancy = reg.gauge(
            "serve_group_occupancy",
            "live request rows / stacked row slots across active groups")
        self._m_cancelled = reg.counter(
            "serve_cancelled_total", "requests retired by cancel()")
        self._m_early = reg.counter(
            "serve_early_exit_total",
            "requests retired early by the RetirePolicy (converged rows)")
        self._m_saved_nfe = reg.counter(
            "serve_saved_nfe_total",
            "network evals saved by early exit (budgeted minus spent)")
        self._m_tiles = reg.counter(
            "serve_eps_tiles_total",
            "eps tile forwards run: tile-loop trips times eps calls of "
            "each group step, summed over devices")
        self._m_tile_rows = reg.counter(
            "serve_eps_tile_rows_total",
            "row slots of the eps tiles run (tiles times the tile's rows)")
        self._m_moe = reg.counter(
            "serve_moe_assignments_total",
            "token-expert pairs the held experts of a dropless MoE computed "
            "at valid positions, over every layer and NFE")
        self._h_queue_wait = reg.histogram(
            "serve_queue_wait_seconds", "submit -> admission (join or fresh)")
        self._h_row_err = reg.histogram(
            "serve_row_err", "local-error estimate at row retirement",
            edges=_ERR_EDGES)
        self._h_solve = reg.histogram(
            "serve_solve_seconds",
            "per-request group solve time since its own admission")
        self._h_step = reg.histogram(
            "serve_step_seconds", "one group step, dispatch to ready")
        self._h_tick = reg.histogram(
            "serve_tick_seconds", "one full scheduler tick")

    # ---- int views over the registry
    @property
    def ticks(self) -> int:
        """Scheduler ticks executed (metric)."""
        return int(self._m_ticks.value)

    @property
    def wasted_row_steps(self) -> int:
        """Steps run on already-finished rows (metric; always 0)."""
        return int(self._m_wasted.value)

    @property
    def joined_requests(self) -> int:
        """Requests admitted by joining an in-flight group (metric)."""
        return int(self._m_joined.value)

    # ------------------------------------------------------------- plans
    def _plan(self, solver: str, nfe: int, eta: float | None) -> SolverPlan:
        if solver == "ddim_eta" and eta is None:
            raise ValueError("Request(solver='ddim_eta') requires an explicit "
                             "eta= (eta=0 deterministic, eta=1 ancestral)")
        key_ = (solver, nfe, eta)
        if key_ not in self._plans:
            if solver.lower() == "pndm":
                n_grid = max(4, nfe - _PNDM_WARMUP_EXTRA)
            else:
                n_grid = max(1, nfe // solver_stages(solver))
            ts = get_timesteps(self.sde, n_grid, self.schedule)
            kw = {"eta": eta} if solver == "ddim_eta" else {}
            if self.retire is not None:
                # uniform request across mixed traffic: families without an
                # embedded pair ignore it (their flag stays False)
                kw["error_estimate"] = True
            # coefficient construction is memoized process-wide (keyed on
            # family + schedule fingerprint + grid + kwargs), so admission
            # of a known (solver, nfe, eta) never re-runs the float64
            # host precompute
            plan = cached_make_plan(solver, self.sde, ts, **kw)
            if plan.method == "ab":
                plan = dataclasses.replace(plan, fused=True)
            self._plans[key_] = plan
        return self._plans[key_]

    # --------------------------------------------------------- executors
    def _shardings(self, plan: SolverPlan, state):
        """(plan, state) NamedSharding trees for this engine's mesh (or
        (None, None) unsharded). NamedShardings are shape-agnostic, so the
        same trees place any batch size whose request axis divides the data
        axes -- which admission's group-size rounding guarantees."""
        if self.mesh is None:
            return None, None
        return SAMPLER._request_shardings(plan, state, self.mesh)

    def _executor(self, sig, plan: SolverPlan, state) -> tuple[Callable, float]:
        """AOT-compiled single step for this (signature, batch, seq_len,
        mesh).

        ``k`` is a traced argument, so ONE trace serves every step index of
        every group with this cache key; compiling ahead of time (instead of
        on first call) is what lets compile cost be measured apart from
        solve time. Under a mesh the executor is jitted with explicit
        in/out shardings (params replicated, request-axis leaves over the
        data axes), and the mesh fingerprint keys the cache so a mesh swap
        can never silently reuse a stale placement."""
        key_ = (sig, state.x.shape[0], state.x.shape[1], self._mesh_key)
        if key_ in self._compiled:
            self._m_cache_hits.inc()
            return self._compiled[key_], 0.0
        self._m_cache_misses.inc()
        cfg, mesh = self.cfg, self.mesh
        counting = self._counts_moe(plan)

        def run(params, plan_arg, k, st, lens):
            taps = [] if counting else None
            new = SAMPLER.step(plan_arg, k, st,
                               DLM.make_tiled_eps_fn(params, cfg,
                                                     valid_len=lens,
                                                     mesh=mesh,
                                                     held_counts=taps),
                               mesh=mesh)
            return (new, sum(taps)) if counting else new

        # k is lowered as a PER-ROW (R,) step vector: one trace serves both
        # groups admitted whole (all entries equal -- bitwise identical to a
        # scalar k) and post-join groups whose rows run at their own counts.
        # lens is the PER-ROW (R,) true-length vector: bucketed rows mask
        # their padded tail keys out of attention, so sample content is
        # independent of the bucket the row landed in (full-length rows pass
        # lens == seq_len, an all-true mask).
        rows = jax.ShapeDtypeStruct((state.x.shape[0],), jnp.int32)
        t0 = time.perf_counter()
        if self.mesh is None:
            jitted = jax.jit(run)
        else:
            from ..sharding.rules import step_index_specs, to_shardings
            plan_sh, state_sh = self._shardings(plan, state)
            param_sh = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            row_sh = to_shardings(step_index_specs(rows, self.mesh),
                                  self.mesh)
            jitted = jax.jit(run, in_shardings=(param_sh, plan_sh, row_sh,
                                                state_sh, row_sh),
                             out_shardings=(state_sh, row_sh) if counting
                             else state_sh)
        with self.tracer.span("compile"):
            lowered = jitted.lower(self._params_exec, plan, rows, state, rows)
            # the row moves ending at this batch are lowered while the step
            # program compiles, and compile beside it: XLA's compile
            # releases the GIL
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                step = pool.submit(lowered.compile)
                moves = [] if self.mesh is not None \
                    else [pool.submit(lo.compile)
                          for lo in self._row_moves(plan, state)]
                compiled = step.result()
                for f in moves:
                    f.result()
        compile_s = time.perf_counter() - t0
        self._m_compile_s.inc(compile_s)
        self._compiled[key_] = compiled
        return compiled, compile_s

    def _count_tiles(self, g: _Group, ks: list) -> None:
        """Count the eps tiles one step of ``g`` at the per-row step
        vector ``ks`` runs: each device loops over its own rows in tiles
        of the bucket's :func:`~repro.diffusion.lm.tile_rows`, once per
        eps call of the step."""
        tile = DLM.tile_rows(g.seq_len)
        per_device = len(g.rows) // self._data_size
        tiles = self._data_size * -(-per_device // tile) \
            * SAMPLER.step_evals(g.plan, ks)
        self._m_tiles.inc(tiles)
        self._m_tile_rows.inc(tiles * tile)

    def _counts_moe(self, plan: SolverPlan) -> bool:
        """Whether the step returns, beside the state, the per-row count of
        assignments the held experts of a dropless MoE computed. pndm runs
        its evals under a ``lax.cond``, out of the count's reach."""
        return self.cfg.moe is not None and self.cfg.moe.dropless \
            and plan.method != "pndm"

    def _row_moves(self, plan: SolverPlan, state) -> list:
        """The row moves that end at this batch of ``R`` rows, lowered: the
        splice into it from every smaller count (a join) and the gather
        into it from every larger count (a compaction, or the pick of
        finished rows to decode), each a plan half and a state half. Once
        they are compiled, a warm engine's boundary pass compiles nothing,
        however the groups join and shrink. Unsharded only: placed inputs
        key other programs."""
        r = state.x.shape[0]

        def at(n):          # (coeffs, ts, state) shapes at n rows
            def sds(a, axis=0):
                shape = a.shape if axis is None \
                    else a.shape[:axis] + (n,) + a.shape[axis + 1:]
                return jax.ShapeDtypeStruct(shape, a.dtype,
                                            weak_type=a.weak_type)
            return ({k: sds(v) for k, v in plan.coeffs.items()},
                    sds(plan.ts),
                    SAMPLER.SamplerState(x=sds(state.x),
                                         hist=sds(state.hist, 1),
                                         key=sds(state.key),
                                         k=sds(state.k, None),
                                         err=sds(state.err)))
        out = []
        for n in range(1, r):
            (c, t, st), (ac, at_, ast) = at(n), at(r - n)
            out += [concat_plan_rows.lower(c, t, ac, at_),
                    SAMPLER.concat_state_rows.lower(st, ast)]
        idx = jax.ShapeDtypeStruct((r,), jnp.int32)
        for n in range(r + 1, self._chunk_cap + 1):
            c, t, st = at(n)
            out += [gather_plan_rows.lower(c, t, idx),
                    SAMPLER.gather_state_rows.lower(st, idx)]
        return out

    def _place(self, plan: SolverPlan, state) -> tuple:
        """(plan, state) committed to the mesh's request-axis placement
        (as they are, unsharded)."""
        plan_sh, state_sh = self._shardings(plan, state)
        if plan_sh is None:
            return plan, state
        return jax.device_put(plan, plan_sh), jax.device_put(state, state_sh)

    def _decode_rows(self, g: _Group, idx: list) -> np.ndarray:
        """Tokens of rows ``idx`` of ``g``: its whole iterate when ``idx``
        is every row, else the rows' gather."""
        x = g.state.x if idx == list(range(len(g.rows))) \
            else SAMPLER.take_state_rows(g.state, idx).x
        # repro: allow[RL001] finished rows leave the device here by design
        return np.asarray(DLM.decode_tokens(self._params_exec, self.cfg, x))

    # -------------------------------------------------------- scheduling
    def _bucket_len(self, seq_len: int) -> int:
        """Bucketed solve length: the first edge >= seq_len, or the exact
        length when no edge fits (or bucketing is off)."""
        if self.seq_len_buckets is not None:
            for edge in self.seq_len_buckets:
                if seq_len <= edge:
                    return edge
        return seq_len

    def submit(self, request: Request) -> None:
        """Validate and enqueue; the request is admitted at the next tick --
        into a fresh group, or spliced into an in-flight one of its bucket.
        Validation (unknown solver, ddim_eta without eta) raises
        HERE, before the request enters the queue, so a bad request can never
        strand already-queued work mid-admission. The submit timestamp
        anchors the request's absolute deadline (``deadline_s`` is relative
        to NOW, not to admission)."""
        if request.seq_len < 1:
            raise ValueError(f"Request.seq_len must be >= 1, got "
                             f"{request.seq_len}")
        if request.nfe < 1:
            raise ValueError(f"Request.nfe must be >= 1, got {request.nfe}")
        plan = self._plan(request.solver, request.nfe,
                          request.eta if request.solver == "ddim_eta" else None)
        # perf_counter everywhere: one monotonic clock domain for deadlines,
        # solve timing and compile timing (mixing in wall-clock time.time()
        # was the old LM-loop bug -- negative latencies across a clock step).
        self._pending.append(_Pending(request, plan, time.perf_counter(),
                                      self._bucket_len(request.seq_len)))
        self._m_submitted.inc()
        self._g_queue.set(len(self._pending))

    @staticmethod
    def _abs_deadline(req: Request, t_submit: float) -> float:
        return math.inf if req.deadline_s is None else t_submit + req.deadline_s

    def _group_key(self, g: _Group) -> tuple:
        """Urgency ordering shared by ``_select`` and the join/compact
        boundary pass: effective priority desc (starvation aging), earliest
        absolute deadline, admission order."""
        return (-(g.priority + g.skipped // self.aging_ticks),
                g.deadline, g.arrival)

    def _evict_expired(self, now: float) -> None:
        """Deadline enforcement (``enforce_deadlines=True``): shed pending
        requests and retire mid-flight rows whose absolute deadline has
        passed. Evicted rows are marked ``done`` so the ordinary boundary
        pass recycles their slots (join refill / ``take_rows`` compaction /
        structural filler) exactly like normally-retired rows; a group left
        with no live rows is dropped whole. Each eviction emits a
        ``deadline_exceeded`` Result (drained by this tick) and increments
        ``serve_deadline_evicted_total``. Never raises: a deadline storm
        degrades the affected requests only."""
        empty = np.zeros(0, np.int32)
        still = deque()
        while self._pending:
            p = self._pending.popleft()
            if self._abs_deadline(p.req, p.t_sub) < now:
                self._m_evicted.inc()
                self._h_queue_wait.observe(now - p.t_sub)
                self._boundary_results.append(Result(
                    p.req.uid, empty, 0.0, nfe=0,
                    queue_wait_s=now - p.t_sub, deadline_exceeded=True))
            else:
                still.append(p)
        self._pending = still
        for g in list(self._active):
            for r in g.rows:
                if r.done or r.pad or not (r.deadline < now):
                    continue
                r.done = True
                self._m_evicted.inc()
                self._h_queue_wait.observe(r.wait_s)
                self._boundary_results.append(Result(
                    r.req.uid, empty, g.solve_s - r.solve_s0, nfe=0,
                    compile_s=g.compile_s, queue_wait_s=r.wait_s,
                    deadline_exceeded=True))
            if not any(not r.done for r in g.rows):
                self._active.remove(g)

    def cancel(self, uid: int) -> bool:
        """Cancel request ``uid``: drop it from the pending queue, or retire
        its mid-flight row through the deadline-eviction machinery (the slot
        recycles via join/compaction at the next boundary). Emits a Result
        flagged ``cancelled=True`` (drained by the next tick; ``busy`` stays
        True until then so a driver loop always delivers it). Returns False
        when ``uid`` is unknown -- already finished, already evicted, or
        never submitted -- and cancellation is a no-op (the original Result
        stands). Runs on the scheduler thread (the driver routes cancels
        through its inbox)."""
        empty = np.zeros(0, np.int32)
        now = time.perf_counter()
        for p in list(self._pending):
            if p.req.uid == uid:
                self._pending.remove(p)
                self._g_queue.set(len(self._pending))
                self._m_cancelled.inc()
                self._h_queue_wait.observe(now - p.t_sub)
                self._boundary_results.append(Result(
                    uid, empty, 0.0, nfe=0, queue_wait_s=now - p.t_sub,
                    cancelled=True))
                return True
        for g in list(self._active):
            for r in g.rows:
                if r.pad or r.done or r.req.uid != uid:
                    continue
                r.done = True
                self._m_cancelled.inc()
                self._h_queue_wait.observe(r.wait_s)
                self._boundary_results.append(Result(
                    uid, empty, g.solve_s - r.solve_s0, nfe=0,
                    compile_s=g.compile_s, queue_wait_s=r.wait_s,
                    cancelled=True))
                if not any(not row.done for row in g.rows):
                    self._active.remove(g)
                return True
        return False

    def _retire_converged(self) -> None:
        """Early-exit pass (``retire`` policy set): retire rows whose local
        error estimate has converged, BEFORE the boundary pass rebuilds
        groups -- a freed slot is a join slot the very same tick.

        A row is eligible once it has taken ``min_k`` of its OWN steps and
        before its natural horizon; convergence is the policy's pure per-row
        decision over ``(err, |x|_inf)`` -- rows whose plans carry no
        embedded pair report err=+inf and never pass. Retired rows emit a
        full Result (their iterate IS the converged sample, decoded and
        masked to the true seq_len) flagged ``early_exit=True`` with
        ``nfe`` = evals actually spent; the saved difference feeds
        ``serve_saved_nfe_total``. Groups whose plans carry no estimates are
        skipped without touching the device."""
        pol = self.retire
        for g in list(self._active):
            if not g.plan.error_estimate:
                continue
            cand = [i for i, r in enumerate(g.rows)
                    if not r.done and not r.pad
                    and pol.min_k <= g.k - r.k0 < r.n_steps]
            if not cand:
                continue
            # repro: allow[RL001] early-exit boundary: err fetch gates retirement
            err = np.asarray(jax.device_get(g.state.err), np.float64)
            if pol.norm == "rel":
                x = g.state.x
                # repro: allow[RL001] boundary fetch, amortized over the whole group
                x_inf = np.asarray(jnp.max(
                    jnp.abs(x), axis=tuple(range(1, x.ndim))), np.float64)
            else:
                x_inf = np.zeros(len(g.rows))
            mask = pol.converged(err[cand], x_inf[cand])
            hit = [i for i, m in zip(cand, mask) if m]
            if not hit:
                continue
            toks = self._decode_rows(g, hit)
            for j, i in enumerate(hit):
                r = g.rows[i]
                r.done = True
                k_own = g.k - r.k0
                spent = _spent_nfe(g.plan.method, r, k_own)
                self._m_completed.inc()
                self._m_early.inc()
                self._m_saved_nfe.inc(max(0, r.nfe - spent))
                self._h_row_err.observe(float(err[i]))
                self._h_queue_wait.observe(r.wait_s)
                lat = g.solve_s - r.solve_s0
                self._h_solve.observe(lat)
                self._boundary_results.append(Result(
                    r.req.uid, toks[j][:r.req.seq_len], lat, nfe=spent,
                    compile_s=g.compile_s, queue_wait_s=r.wait_s,
                    early_exit=True, final_err=float(err[i]),
                    moe_assignments=r.moe if self._counts_moe(g.plan)
                    else None))
            if not any(not r.done for r in g.rows):
                self._active.remove(g)

    def _admit(self) -> None:
        """Admit everything pending (step-boundary admission).

        Two phases, both ordered by the same urgency key (priority desc,
        deadline asc):

        1. *Boundary pass*: every in-flight group is offered the pending
           requests of its bucket and priority whose grids fit its horizon,
           and they JOIN it up to ``max_group`` live rows (retired rows
           become slots). A group carrying retired rows that nothing refills
           compacts down to its survivors (:meth:`_rebuild`). Groups are
           visited in ``_select``'s urgency order, so the most urgent
           in-flight work gets the most urgent joiners.
        2. *Fresh groups*: remaining pending requests bucket by
           ``(plan.family, bucketed seq_len)`` -- any mix of solver names
           AND NFE budgets whose plans pad+stack is one solve (ragged
           groups) -- and chunk at ``max_group``.

        Under a mesh, each chunk/join target is rounded UP to a multiple of
        the data-axis size with inert filler rows
        (:func:`repro.core.plan.inert_row`): the stacked axis then always
        divides the mesh's data axes, so every group places evenly and the
        executor cache sees only multiple-of-axis batch sizes. Chunking is
        quantized to ``(max_group // axis) * axis`` so rounding can never
        exceed the operator's ``max_group`` bound. Filler rows are born
        ``done`` -- they emit nothing, cost no extra wall-clock in a
        data-parallel step, and are first in line to become join slots.

        With ``enforce_deadlines`` an *eviction pass* runs first: pending
        requests already past their absolute deadline are shed without ever
        forming a group, and mid-flight rows past theirs are marked done
        with a ``deadline_exceeded`` Result -- the ordinary boundary pass
        below then recycles their slots through the SAME ``take_rows``
        join/compaction path every retired row goes through."""
        now = time.perf_counter()
        if self.enforce_deadlines:
            with self.tracer.span("evict"):
                self._evict_expired(now)
        if self.retire is not None:
            with self.tracer.span("retire"):
                self._retire_converged()
        buckets: dict = {}
        while self._pending:
            p = self._pending.popleft()
            buckets.setdefault((p.plan.family, p.s_len), []).append(p)
        self._g_queue.set(0)
        for items in buckets.values():
            items.sort(key=lambda it: (-it.req.priority,
                                       self._abs_deadline(it.req, it.t_sub)))
        for g in sorted(self._active, key=self._group_key):
            take = self._joiners(g, buckets.get(g.bucket))
            if take or any(r.done for r in g.rows):
                self._rebuild(g, take, now)
        for (fam, s_len), items in buckets.items():
            for i in range(0, len(items), self._chunk_cap):
                with self.tracer.span("form"):
                    self._form_group(items[i:i + self._chunk_cap], fam,
                                     s_len, now)

    def _form_group(self, chunk: list, fam: str, s_len: int,
                    now: float) -> None:
        """One fresh group from ``chunk`` (same family and bucketed
        seq_len): plans padded to the longest grid and stacked, filler rows
        up to a data-axis multiple, priors drawn, executor looked up (or
        compiled) and the group placed."""
        n_max = max(p.plan.n_steps for p in chunk)
        padded = [pad_plan(p.plan, n_max) for p in chunk]
        rows = [_Row(req=p.req, n_steps=p.plan.n_steps,
                     nfe=p.plan.nfe,
                     deadline=self._abs_deadline(p.req, p.t_sub),
                     wait_s=now - p.t_sub)
                for p in chunk]
        seeds = [p.req.seed for p in chunk]
        n_fill = (-len(chunk)) % self._data_size
        if n_fill:
            filler = inert_row(padded[0])
            padded += [filler] * n_fill
            rows += [_Row(req=None, n_steps=n_max, nfe=0,
                          deadline=math.inf, done=True, pad=True)
                     for _ in range(n_fill)]
            seeds += [0] * n_fill
        sig = padded[0].signature
        plan = stack_plans(padded)
        with self.tracer.span("prior"):
            keys = DLM.request_keys(seeds)
            state = DLM.init_sample_state(
                self.cfg, plan, keys, seq_len=s_len,
                prior_std=self.sde.prior_std(),
                valid_lens=[p.req.seq_len for p in chunk]
                + [s_len] * n_fill)
        fn, compile_s = self._executor(sig, plan, state)
        plan, state = self._place(plan, state)
        reqs = [p.req for p in chunk]
        self._arrivals += 1
        self._active.append(_Group(
            rows=rows, sig=sig, bucket=(fam, s_len), seq_len=s_len,
            plan=plan, state=state, fn=fn,
            n_steps=n_max, compile_s=compile_s,
            priority=max(r.priority for r in reqs),
            deadline=min(r.deadline for r in rows),
            arrival=self._arrivals))

    def _joiners(self, g: _Group, cands: list | None) -> list:
        """The pending requests that join ``g`` at this boundary, removed
        from ``cands`` (the group's admission bucket, urgency-sorted):
        taken from the front up to the group's free slots, skipping any
        whose grid exceeds the group's horizon (they form fresh groups
        instead -- extending the grid would change the signature and
        recompile) or whose priority differs from the group's live rows'
        (a joiner would lift or sink the group under ``steps_per_tick``).
        Where the group or a joiner carries a deadline, joiners only fill
        the free slots of the live rows' last row tile (the bucket's
        :func:`~repro.diffusion.lm.tile_rows` wide): one tile more would
        slow every step of the deadline row. Empty when nothing can join."""
        live = [r for r in g.rows if not r.done]
        cap = self._chunk_cap - len(live)
        if not cands or cap <= 0:
            return []
        prio = max(r.req.priority for r in live)
        deadline = min(r.deadline for r in live)
        room = -len(live) % DLM.tile_rows(g.seq_len)
        take, rest = [], []
        for p in cands:
            due = self._abs_deadline(p.req, p.t_sub)
            if len(take) < cap and p.plan.n_steps <= g.plan.n_steps \
                    and p.req.priority == prio \
                    and (len(take) < room or math.isinf(min(deadline, due))):
                take.append(p)
                deadline = min(deadline, due)
            else:
                rest.append(p)
        cands[:] = rest
        return take

    def _select(self) -> tuple[list[_Group], list[_Group]]:
        """Order active groups by urgency; return (stepped, skipped).

        Urgency key: effective priority desc (priority + skipped //
        aging_ticks, so any group skipped long enough eventually outranks
        everything at a fixed priority -- no starvation), then earliest
        absolute deadline, then admission order. ``steps_per_tick=None``
        steps every group (ordering = dispatch order only)."""
        order = sorted(self._active, key=self._group_key)
        if self.steps_per_tick is None:
            return order, []
        return order[:self.steps_per_tick], order[self.steps_per_tick:]

    def _round_keep(self, g: _Group, live: list[int],
                    n_new: int) -> tuple[list[int], int]:
        """Rebuild arithmetic shared by compaction and joining.

        The rebuilt batch is ``len(live) + n_new`` rounded up to a
        data-axis multiple; the round-up gap is filled with already-retired
        rows kept as structural padding (original filler first, then
        retired requests, lowest index first). Returns ``(keep, n_inert)``:
        the row indices to gather (live + retained filler, original order)
        and how many fresh inert rows must still be allocated when retired
        rows alone cannot cover the gap (only possible while joining --
        compaction's target never exceeds the current batch)."""
        target = len(live) + n_new
        target += (-target) % self._data_size
        fillers = [i for i, r in enumerate(g.rows) if r.done]
        fillers.sort(key=lambda i: (not g.rows[i].pad, i))
        reuse = fillers[:max(0, target - len(live) - n_new)]
        return (sorted(live + reuse),
                target - len(live) - n_new - len(reuse))

    def _rebuild(self, g: _Group, take: list, now: float) -> None:
        """Rebuild ``g`` at a step boundary: splice in the pending requests
        ``take`` (see :meth:`_joiners`), or with none, compact it down to
        its live rows -- a compaction is a join with no joiners.

        The rebuilt batch keeps the surviving rows in their original
        relative order, each carried whole and bitwise-unmoved (coefficients,
        iterate, eps history, per-request key chains): ``take_rows`` of the
        kept rows when the row set changes, then ``join_rows`` appending the
        padded joiners. Under a mesh it rounds up to a data-axis multiple
        (:meth:`_round_keep`), reusing retired rows as structural filler
        before allocating inert rows, and stays within ``max_group``; only
        the final batch is placed (an intermediate gather need not be a
        multiple). Joiner rows record ``k0 = g.k`` (their steps count from
        THIS tick) and ``solve_s0`` (their latency excludes the group's
        past). The executor changes to the cached one of the new batch
        (compiled on first need, charged to ``g.compile_s``), and group
        urgency is recomputed from the LIVE rows, so a retired urgent row's
        priority/deadline stops preempting other groups on behalf of
        best-effort leftovers."""
        live = [i for i, r in enumerate(g.rows) if not r.done]
        keep, n_inert = self._round_keep(g, live, len(take))
        # a retired row the rebuild keeps is structural filler: not waste,
        # and an open join slot
        for r in g.rows:
            r.pad = r.pad or r.done
        if not take and len(keep) == len(g.rows):
            # nothing joins and nothing shrinks: the group already sits at
            # the smallest placeable multiple of the data axis (mesh only:
            # unsharded groups always shrink)
            return
        with self.tracer.span("join" if take else "compact"):
            if keep != list(range(len(g.rows))):
                g.plan = take_rows(g.plan, keep)
                g.state = SAMPLER.take_state_rows(g.state, keep)
                g.rows = [g.rows[i] for i in keep]
            if take:
                padded = [pad_plan(p.plan, g.plan.n_steps) for p in take]
                seeds = [p.req.seed for p in take]
                g.rows += [_Row(req=p.req, n_steps=p.plan.n_steps,
                                nfe=p.plan.nfe,
                                deadline=self._abs_deadline(p.req, p.t_sub),
                                k0=g.k, solve_s0=g.solve_s,
                                wait_s=now - p.t_sub) for p in take]
                if n_inert:
                    padded += [inert_row(padded[0])] * n_inert
                    seeds += [0] * n_inert
                    g.rows += [_Row(req=None, n_steps=0, nfe=0,
                                    deadline=math.inf, done=True, pad=True,
                                    k0=g.k) for _ in range(n_inert)]
                with self.tracer.span("prior"):
                    add_state = DLM.init_sample_state(
                        self.cfg, stack_plans(padded),
                        DLM.request_keys(seeds), seq_len=g.seq_len,
                        prior_std=self.sde.prior_std(),
                        valid_lens=[p.req.seq_len for p in take]
                        + [g.seq_len] * n_inert)
                g.plan = join_rows(g.plan, padded)
                g.state = SAMPLER.join_state_rows(g.state, add_state)
            g.plan, g.state = self._place(g.plan, g.state)
            live_rows = [r for r in g.rows if not r.done]
            g.n_steps = max(r.k0 + r.n_steps for r in live_rows)
            g.priority = max(r.req.priority for r in live_rows)
            g.deadline = min(r.deadline for r in live_rows)
            g.fn, compile_s = self._executor(g.sig, g.plan, g.state)
            g.compile_s += compile_s
        if take:
            self._m_joined.inc(len(take))
        else:
            self._m_compactions.inc()

    @property
    def busy(self) -> bool:
        """True while any request is pending admission or mid-solve, or a
        boundary Result (eviction/cancellation/early exit) awaits drain."""
        return bool(self._pending or self._active or self._boundary_results)

    def reset(self) -> None:
        """Abort all pending and in-flight work (queues cleared; the plan and
        executor caches survive -- they are pure and reusable). This is the
        recovery point after a failed tick leaves group state unreliable:
        the driver calls it before failing the affected requests' futures."""
        self._pending.clear()
        self._active.clear()
        self._boundary_results.clear()
        self._g_queue.set(0)
        self._g_groups.set(0)
        self._g_occupancy.set(0.0)

    @property
    def num_executors(self) -> int:
        """Compiled executors alive -- one per (plan.signature, batch,
        seq_len, mesh fingerprint); growth during steady-state traffic means
        recompilation."""
        # repro: allow[RL003] GIL-atomic len() for stats; one-tick staleness is fine
        return len(self._compiled)

    def tick(self, *, on_step=None, stream_decode: bool = False) -> list[Result]:
        """One scheduler tick: admit pending requests (joining in-flight
        groups of their bucket, else forming fresh ones), advance
        the selected groups one solver step each, emit Results for rows
        that finished.

        All selected group steps are dispatched before any is blocked on, so
        on async backends the device overlaps them; each group's ``solve_s``
        is the elapsed time from its dispatch to its step being ready (what a
        client of that group observes). Every group steps with a per-row
        ``k`` vector (row ``i`` at ``g.k - k0``), so joiners and veterans
        advance on their own grids in one executor call. A row's Result is
        emitted from the tick its OWN step count completes -- in a ragged
        group that is before the group drains -- with ``latency_s`` = the
        group's solve time since the row's admission and the row's true
        ``nfe``. Groups with only finished rows are retired; groups left
        with retired rows rebuild (join or compact) at the next tick's
        admission boundary, before they step again.

        Spans on ``self.tracer``: ``admit`` (inside it ``evict``,
        ``retire``, ``join``, ``compact``, ``form``, and ``prior`` and
        ``compile`` beneath those), ``dispatch``, ``step_wait`` per group,
        ``decode`` and ``fanout``. No span covers the tick as a whole: a
        profile names each idle stretch of the device by the innermost
        span open over it."""
        t_tick = time.perf_counter()
        with self.tracer.span("admit"):
            self._admit()
        self._m_ticks.inc()
        finished: list[Result] = []
        if self._boundary_results:          # deadline enforcement this tick
            finished += self._boundary_results
            self._boundary_results = []
        stepped, skipped = self._select()
        for g in skipped:
            g.skipped += 1
        dispatched = []
        with self.tracer.span("dispatch"):
            for g in stepped:
                g.skipped = 0
                # structural filler rows (pad) are free capacity in a
                # data-parallel step, not waste; only retired REQUEST rows
                # that keep stepping count. The admission-time boundary
                # pass has already joined over / compacted away / pad-marked
                # every retired row, so this stays zero.
                self._m_wasted.inc(sum(
                    r.done and not r.pad for r in g.rows))
                ks = [g.k - r.k0 for r in g.rows]
                self._count_tiles(g, ks)
                k_vec = jnp.asarray(ks, jnp.int32)
                lens_vec = jnp.asarray(
                    [r.req.seq_len if r.req is not None else g.seq_len
                     for r in g.rows], jnp.int32)
                t0 = time.perf_counter()
                out = g.fn(self._params_exec, g.plan, k_vec, g.state,
                           lens_vec)
                g.state, held = out if self._counts_moe(g.plan) \
                    else (out, None)
                dispatched.append((g, t0, held))
        for g, t0, held in dispatched:
            # rows: live request rows; slots: rows the executor steps
            with self.tracer.span("step_wait",
                                  rows=sum(not r.done for r in g.rows),
                                  slots=len(g.rows), seq=g.seq_len):
                # repro: allow[RL001] THE documented boundary sync: one wait per
                # group-step after all groups dispatched (see module docstring)
                jax.block_until_ready(g.state.x)
                if held is not None:
                    # repro: allow[RL001] R ints of the step just waited on
                    held = np.asarray(held)
                    live = [i for i, r in enumerate(g.rows)
                            if not (r.done or r.pad)]
                    for i in live:
                        g.rows[i].moe += int(held[i])
                    self._m_moe.inc(int(sum(held[i] for i in live)))
            dt_step = time.perf_counter() - t0
            g.solve_s += dt_step
            self._h_step.observe(dt_step)
            g.k += 1
            newly = [i for i, r in enumerate(g.rows)
                     if not r.done and r.k0 + r.n_steps == g.k]
            # decode against the as-placed params (replicated under a mesh):
            # a data-sharded iterate composes with them eagerly, so the
            # sharded and unsharded paths share one decode expression
            stream_toks = err_v = None
            want_toks = on_step is not None and stream_decode
            # one host pull of the per-row error estimates serves both the
            # step event and natural-finish final_err (plans without
            # embedded pairs skip the transfer entirely)
            want_err = g.plan.error_estimate and (on_step is not None
                                                  or newly)
            if want_toks or want_err:
                with self.tracer.span("decode"):
                    if want_toks:
                        # repro: allow[RL001] opt-in stream decode: the caller
                        # chose per-step token delivery over peak throughput
                        stream_toks = np.asarray(DLM.decode_tokens(
                            self._params_exec, self.cfg, g.state.x))
                    if want_err:
                        # repro: allow[RL001] single err pull serves step event + final_err
                        err_v = np.asarray(jax.device_get(g.state.err),
                                           np.float64)
            if on_step is not None:
                real = g.real_idx
                event = StepEvent(
                    uids=g.uids, k=g.k, n_steps=g.n_steps,
                    tokens=stream_toks[real] if stream_toks is not None
                    else None,
                    row_steps=tuple(g.rows[i].n_steps for i in real),
                    row_k=tuple(g.k - g.rows[i].k0 for i in real),
                    row_seq_lens=tuple(g.rows[i].req.seq_len for i in real),
                    row_err=tuple(float(err_v[i]) for i in real)
                    if err_v is not None else None)
                with self.tracer.span("fanout"):
                    on_step(event)
            if newly:
                with self.tracer.span("decode"):
                    # decode ONLY the finished rows unless a full partial
                    # decode already exists (ragged groups would otherwise
                    # pay one full-batch decode per distinct member NFE)
                    new_toks = (stream_toks[newly] if stream_toks is not None
                                else self._decode_rows(g, newly))
                    for j, i in enumerate(newly):
                        row = g.rows[i]
                        row.done = True
                        # bucketed admission: mask the solve's tail
                        # positions back to the request's true seq_len.
                        # final_err is None (not +inf) when no estimate
                        # exists: Results serialize to strict JSON, which
                        # has no Infinity literal.
                        f_err = None
                        if err_v is not None and math.isfinite(err_v[i]):
                            f_err = float(err_v[i])
                        res = Result(
                            row.req.uid, new_toks[j][:row.req.seq_len],
                            g.solve_s - row.solve_s0, nfe=row.nfe,
                            compile_s=g.compile_s, queue_wait_s=row.wait_s,
                            final_err=f_err,
                            moe_assignments=row.moe if held is not None
                            else None)
                        self._m_completed.inc()
                        self._h_queue_wait.observe(res.queue_wait_s)
                        self._h_solve.observe(res.latency_s)
                        finished.append(res)
            if not any(not r.done for r in g.rows):
                self._active.remove(g)
        self._g_groups.set(len(self._active))
        slots = sum(len(g.rows) for g in self._active)
        live = sum(sum(not r.done for r in g.rows) for g in self._active)
        self._g_occupancy.set(live / slots if slots else 0.0)
        self._h_tick.observe(time.perf_counter() - t_tick)
        return finished

    def serve(self, requests: list[Request], *, on_step=None,
              stream_decode: bool = False) -> list[Result]:
        """Submit ``requests`` and run the scheduler until all solves finish.

        More requests may be ``submit()``-ed (e.g. from ``on_step``) while
        this drains; they are admitted at the next step boundary.

        Validation is all-or-nothing for this call: if any request is
        invalid, none of this call's requests stay queued."""
        n0 = len(self._pending)
        try:
            for r in requests:
                self.submit(r)
        except Exception:
            while len(self._pending) > n0:
                self._pending.pop()
            raise
        results: list[Result] = []
        while self.busy:
            results += self.tick(on_step=on_step, stream_decode=stream_decode)
        return results
