"""Repo-specific policy for the analysis pass: which files are hot path
(RL001), and who owns which serving-stack attribute (RL003).

This is deliberately data, not code: adding a new hot module or a new
engine/driver attribute means editing a table here (and the checkers tell
you when you forgot -- RL003 fails on attributes missing from the ownership
table). Scope patterns are regexes matched against the END of the posix
path, so the tables work from any checkout root and on the test fixtures.
"""
from __future__ import annotations

import dataclasses


# ------------------------------------------------------------- RL001 scopes
# Sub-check groups. "sync" = explicit host syncs (.item(), block_until_ready,
# jax.device_get, np.asarray/np.array of device values, print); "coerce" =
# float()/int()/bool() of possibly-device values; "branch" = Python `if`/
# `while` on a traced array (implicit bool() sync + retrace hazard).
SYNC = "sync"
COERCE = "coerce"
BRANCH = "branch"
ALL_CHECKS = frozenset({SYNC, COERCE, BRANCH})
SYNC_ONLY = frozenset({SYNC})


@dataclasses.dataclass(frozen=True)
class HotScope:
    """One hot-path region: a path pattern plus what is in scope there.

    ``functions``: only these def names are hot (None = whole module).
    ``entry``: ``(Class, method)`` -- the hot region is every method of
    Class reachable from that entry through self-calls (used for the engine
    tick path, so scheduling helpers stay covered as they are added).
    """
    pattern: str
    checks: frozenset = ALL_CHECKS
    functions: tuple | None = None
    entry: tuple | None = None


# The solver executor (everything in it runs under jit per step), the plan
# splice primitives serving calls between steps, the kernels, the
# observability fast path (spans/metrics sit inside the tick), and the
# engine tick path itself.
HOT_SCOPES = (
    HotScope(r"core/sampler\.py$"),
    HotScope(r"core/plan\.py$", functions=(
        # splice primitives + signature/role helpers run per tick inside the
        # serving loop; plan_* builders are host-side float64 precompute by
        # contract and are RL004's concern instead.
        "astype", "stack_plans", "pad_plan", "take_rows", "join_rows",
        "inert_row", "_rowless_signature", "_leaf_role", "signature",
        "family", "n_steps", "batch", "history_len")),
    HotScope(r"kernels/[^/]+\.py$"),
    HotScope(r"obs/(trace|metrics)\.py$", checks=SYNC_ONLY),
    HotScope(r"serving/engine\.py$",
             entry=("DiffusionServeEngine", "tick")),
)

# jnp functions that return host scalars/metadata, not device arrays --
# fine inside an `if` test.
HOST_SAFE_JNP = frozenset({
    "ndim", "shape", "size", "dtype", "issubdtype", "isdtype",
    "result_type", "iscomplexobj", "isscalar"})


# ---------------------------------------------------------- RL003 ownership
@dataclasses.dataclass(frozen=True)
class Ownership:
    """Thread-ownership declaration for one serving-stack class.

    Buckets (fnmatch patterns over attribute names):
      ``config``    -- immutable after __init__; readable from any thread,
                       never reassigned outside __init__.
      ``scheduler`` -- scheduler-thread-only state; never touched from a
                       transport-reachable method.
      ``locked``    -- shared state; every access must sit inside
                       ``with self.<lock>:``  (except in __init__).
      ``atomic``    -- intrinsically thread-safe objects (queue.Queue,
                       threading.Event, metrics handles): any thread, no lock.

    ``transport_entries`` are the public thread-safe entry points; methods
    reachable from them (through self-calls and ``delegates``) inherit the
    transport context and must obey the scheduler-only restriction. ``"*"``
    means every method. ``scheduler_entries`` seed the scheduler context
    (the tick loop). ``delegates`` maps attribute -> class for cross-object
    call-graph edges (the driver holding the engine).
    """
    lock: str | None = None
    transport_entries: tuple = ()
    scheduler_entries: tuple = ()
    config: tuple = ()
    scheduler: tuple = ()
    locked: tuple = ()
    atomic: tuple = ()
    delegates: dict = dataclasses.field(default_factory=dict)


OWNERSHIP = {
    # The engine is single-threaded by contract: the driver's scheduler
    # thread owns it. Anything the driver's transport surface reads off it
    # must be a metrics handle (atomic) or carry an explicit allow.
    "DiffusionServeEngine": Ownership(
        scheduler_entries=("tick", "serve", "submit", "cancel", "reset",
                           "busy"),
        config=("cfg", "sde", "schedule", "max_group", "steps_per_tick",
                "aging_ticks", "seq_len_buckets", "mesh", "_mesh_key",
                "_data_size", "_chunk_cap", "params", "_params_exec",
                "enforce_deadlines", "retire", "metrics", "tracer"),
        scheduler=("_plans", "_compiled", "_pending", "_active", "_arrivals",
                   "_boundary_results"),
        atomic=("_m_*", "_g_*", "_h_*"),
    ),
    "ServeDriver": Ownership(
        lock="_lock",
        transport_entries=("submit", "submit_async", "cancel", "stats",
                           "start", "stop", "__enter__", "__exit__"),
        scheduler_entries=("_run",),
        config=("engine", "stream_decode", "idle_wait_s", "max_pending",
                "metrics"),
        locked=("_streams", "_thread"),
        atomic=("_inbox", "_stop", "_lock", "_m_*", "_h_*"),
        delegates={"engine": "DiffusionServeEngine"},
    ),
    # Registration is the only locked registry operation; the metric handles
    # themselves are single-writer lock-free by design.
    "MetricsRegistry": Ownership(
        lock="_lock",
        transport_entries=("*",),
        locked=("_metrics",),
        atomic=("_lock",),
    ),
}


# ---------------------------------------------------------- RL004 registries
# The coefficient-role registries in core/plan.py (PR 8's registration
# guard) that every plan_* coefficient key must be classifiable by, and the
# modifier set allowed to overlap the primary roles.
ROLE_REGISTRIES = ("_PER_STEP_COEFFS", "_PER_KNOT_COEFFS", "_STATIC_COEFFS")
MODIFIER_REGISTRIES = ("_TIME_LIKE",)
