"""Declarative op registry: one OpSpec per kernel, one generic call path.

The paper's thesis is that transformations become tractable when they are
*systematized* — a taxonomy of reusable transformations instead of ad-hoc
per-kernel rewrites (§2–§5).  FBLAS and TAPA make the same argument for
kernel *libraries*: a uniform module/interface contract over streaming
kernels is what makes the library composable and extensible.  Through
PRs 2–4 our dispatch layer grew the opposite way: every op hand-wired its
own eligibility check, reference lowering, custom VJP, route counters,
tuned-plan key, and tune-space hookup across four modules, so adding a
kernel meant a five-file scavenger hunt.

This module is the systematization.  Each op is a single :class:`OpSpec`
declaring:

* ``reference`` — the pure-XLA lowering (bit-identical to the pre-dispatch
  model code);
* ``kernel`` — the Pallas lowering (interpret mode on CPU);
* ``eligible`` — the trace-time structural predicate for the kernel route;
* ``plan_shape`` / ``plan_kernel`` — the tuned-plan key schema: the shape
  tuple this op's autotuner entries are keyed by, and (optionally) which
  kernel's plan namespace it shares (``grouped_matmul`` consults
  ``matmul`` plans);
* ``vjp_fwd`` / ``vjp_bwd`` — an optional custom-VJP pair (forward with
  residuals + backward schedule selection) wrapped generically in ONE
  ``jax.custom_vjp`` shared by every differentiable op;
* ``tune`` — a :class:`TuneSpec` (space factory, input builder, timed
  call, default shapes/dtype) the autotuner enumerates *from*, so
  ``tune.tuner`` holds no parallel op tables;
* ``stats_op`` — the route-counter scope;
* ``example`` / ``bad_example`` — a canonical dispatch-level call and a
  known-ineligible one, consumed by the registry completeness tests.

``call()`` is the one generic code path replacing the five hand-rolled
copies: eligibility → tuned-plan resolution (exact → nearest → heuristic,
tagged with its source so route counters and ``tune.cache.lookup_stats``
can never disagree) → the level gate (a tuned entry that says "the
reference lowering wins here" is honored under "auto"; an explicit
"kernels" policy forces the Pallas lowering, keeping tuned tile geometry)
→ route counting → the kernel (custom-VJP'd when declared) or reference
lowering.

Policy *resolution* (DispatchPolicy / env / backend gate) stays in
``repro.kernels.dispatch`` — the thin, backward-compatible facade layer —
which passes the collapsed ``mode`` and ``allow_kernels`` decision here.

Op modules register themselves at import; :func:`ensure_registered`
imports the known registration modules so lookups work from any entry
point (dispatch facades, the tuner, tests) without eager kernel imports.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
from collections import Counter
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from ..core.plan import Level
from ..tune.cache import resolve_plan_source

# Registration-module manifest (not an op table: each module declares its
# own OpSpecs; this only says where registrations live so lazy lookups can
# trigger them).  Adding a kernel = adding its ops module here.
_OP_MODULES = (
    "repro.kernels.matmul.ops",
    "repro.kernels.attention.ops",
    "repro.kernels.stencil.ops",
    "repro.kernels.histogram.ops",
    "repro.kernels.nbody.ops",
)


@dataclasses.dataclass(frozen=True)
class TuneSpec:
    """How the autotuner sweeps this op: candidate space, inputs, call."""

    space: Callable[..., list]            # (shape, dtype_bytes, **kw) -> plans
    make_inputs: Callable[..., tuple]     # (shape, dtype) -> call args
    call: Callable[..., Any]              # (args, plan_dict) -> jax value
    default_dtype: Any
    default_shapes: Tuple[Tuple[int, ...], ...]


@dataclasses.dataclass(frozen=True)
class TPContract:
    """One named way an op participates in tensor parallelism.

    The sharding contract an op declares for the mapped (``shard_map``)
    serving region: which dimension of each positional argument is
    device-local (sharded over the tensor-parallel mesh axis; ``None`` =
    replicated / identical on every device), which collective completes
    the op, and which output dimension that collective concatenates.
    ``registry.call`` applies the completing collective itself when a
    :func:`tp_scope` is active, so the mapped region's collectives live
    on exactly one code path — the same one that routes, counts, and
    plans every lowering.  Outside a tp scope the contract is inert: the
    same model code runs sharded and unsharded.

    * ``in_axes`` — per positional arg, the arg dimension sharded over
      the tp axis (``None`` = replicated).  Trailing optional args (e.g.
      quantization scales) may be omitted.
    * ``collective`` — ``"none"`` (output stays device-local, e.g. a
      column-parallel GEMM), ``"psum"`` (output is a partial sum over
      the sharded contraction — row-parallel GEMM all-reduce), or
      ``"all_gather"`` (output shards concatenate along ``gather_axis``
      — the attention ops' heads-local output becoming full-width).
    * ``gather_axis`` — output dim the ``all_gather`` concatenates.
    """

    in_axes: Tuple[Optional[int], ...] = ()
    collective: str = "none"                # none | psum | all_gather
    gather_axis: int = 0

    def __post_init__(self):
        if self.collective not in ("none", "psum", "all_gather"):
            raise ValueError(
                f"TPContract collective must be none|psum|all_gather, "
                f"got {self.collective!r}")


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One kernel's complete dispatch + tuning contract (see module doc)."""

    name: str
    reference: Optional[Callable] = None     # (ctx, *args) -> out
    kernel: Optional[Callable] = None        # (ctx, *args) -> out
    eligible: Optional[Callable] = None      # (statics, *args) -> bool
    plan_shape: Optional[Callable] = None    # (statics, *args) -> key shape
    plan_kernel: Optional[str] = None        # tuned-plan namespace (default: name)
    plan_dtype: Optional[Callable] = None    # (statics, *args) -> key dtype
    #   (default: args[0].dtype; paged-attention ops key on the POOL dtype
    #   so int8-cache plans never transplant onto bf16 pools)
    vjp_fwd: Optional[Callable] = None       # (ctx, *args) -> (out, residuals)
    vjp_bwd: Optional[Callable] = None       # (ctx, residuals, g) -> grads
    tune: Optional[TuneSpec] = None
    stats_op: Optional[str] = None           # route-counter scope (default: name)
    example: Optional[Callable] = None       # (dtype) -> (args, statics)
    bad_example: Optional[Callable] = None   # () -> (args, statics)
    # mesh-awareness: the sharding contracts this op supports inside a
    # shard_map'd serving region, keyed by the call site's ``tp=`` tag
    # ("col" | "row" | "heads" | ...).  An op with no contracts can only
    # be called untagged inside a tp scope.
    tp: Optional[Dict[str, TPContract]] = None

    @property
    def dispatchable(self) -> bool:
        return self.reference is not None


@dataclasses.dataclass(frozen=True)
class OpCtx:
    """Hashable static call context handed to every lowering callable.

    Hashability is the custom-VJP contract: the ctx rides as a nondiff
    argument through the shared ``jax.custom_vjp``, so statics and plan
    values must be hashable (ints/bools/strings/dtypes).
    """

    op: str
    mode: str                                   # kernels | reference | auto
    level: int                                  # resolved Level, as int
    plan: Tuple[Tuple[str, Any], ...] = ()      # resolved tuned kwargs
    statics: Tuple[Tuple[str, Any], ...] = ()   # op-specific static kwargs

    @property
    def kw(self) -> Dict[str, Any]:
        return dict(self.statics)

    @property
    def plan_kwargs(self) -> Dict[str, Any]:
        return dict(self.plan)

    def ops_plan(self) -> Dict[str, Any]:
        """The resolved plan as the kwargs-dict form the ``ops.py``
        wrappers accept (``plan=<dict>`` short-circuits their own cache
        lookup, so a dispatch-level call resolves the plan exactly once)."""
        return {"level": self.level, **dict(self.plan)}


# ------------------------------------------------------------ the registry
_REGISTRY: Dict[str, OpSpec] = {}
_ensured = False


def register(spec: OpSpec) -> OpSpec:
    if not isinstance(spec, OpSpec):
        raise TypeError(f"register() wants an OpSpec, got {type(spec)}")
    _REGISTRY[spec.name] = spec
    return spec


def ensure_registered() -> None:
    """Import every registration module once (idempotent, lazy).

    The flag flips only after every module imported cleanly, so a
    transient import failure is retried on the next lookup instead of
    leaving a permanently half-populated registry."""
    global _ensured
    if _ensured:
        return
    for mod in _OP_MODULES:
        importlib.import_module(mod)
    _ensured = True


def get(name: str) -> OpSpec:
    if name not in _REGISTRY:
        ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown op {name!r}; registered: {sorted(_REGISTRY)}") from None


def ops() -> Dict[str, OpSpec]:
    """All registered OpSpecs, registration order (stable)."""
    ensure_registered()
    return dict(_REGISTRY)


def dispatchable() -> Dict[str, OpSpec]:
    """Ops with a dispatch surface (a reference lowering to route against)."""
    return {n: s for n, s in ops().items() if s.dispatchable}


def tunable() -> Dict[str, OpSpec]:
    """Ops the autotuner sweeps (``tune.tuner`` enumerates from this)."""
    return {n: s for n, s in ops().items() if s.tune is not None}


# ------------------------------------------------------------------- stats
# (op, route) counters, incremented at trace time, plus (op, route, source)
# plan-source counters: ``source`` is the tuned-plan lookup route (exact |
# nearest | heuristic) that produced the routing decision, so
# ``dispatch.stats()`` and ``tune.cache.lookup_stats()`` tell one story —
# e.g. a tuned entry that picks the reference lowering shows up as
# (op, "reference", "exact"), matching the cache's exact-hit count.
_stats: Counter = Counter()
_plan_stats: Counter = Counter()
# (op, route) counters ticked ONLY while a tp_scope is active — the probe
# that proves registry.call fired INSIDE the shard_map'd serving region
# (sharded serving that silently routed outside the mapped region would
# show tp_stats() == {}).
_tp_stats: Counter = Counter()


def reset_stats() -> None:
    _stats.clear()
    _plan_stats.clear()
    _tp_stats.clear()


def stats() -> Dict[Tuple[str, str], int]:
    return dict(_stats)


def plan_source_stats() -> Dict[Tuple[str, str, str], int]:
    return dict(_plan_stats)


def tp_stats() -> Dict[Tuple[str, str], int]:
    return dict(_tp_stats)


@contextlib.contextmanager
def stats_scope():
    """Isolated counter scope: zeroed on entry, restored on exit.

    Tests and probes read routes via the yielded ``stats`` accessor without
    leaking counts into (or absorbing counts from) other test modules.
    """
    saved = Counter(_stats)
    saved_plan = Counter(_plan_stats)
    saved_tp = Counter(_tp_stats)
    reset_stats()
    try:
        yield stats
    finally:
        _stats.clear()
        _stats.update(saved)
        _plan_stats.clear()
        _plan_stats.update(saved_plan)
        _tp_stats.clear()
        _tp_stats.update(saved_tp)


def count_route(op: str, route: str, source: Optional[str] = None) -> None:
    """Public counter hook for op-declared schedules (e.g. the attention
    backward counts its own fused-vs-stash route from inside its VJP)."""
    _stats[(op, route)] += 1
    if source is not None:
        _plan_stats[(op, route, source)] += 1
    if _TP_AXIS is not None:
        _tp_stats[(op, route)] += 1


# ------------------------------------------------------ tensor-parallel scope
# The serving runtime (runtime/tp.py) enters a tp_scope while TRACING the
# shard_map body, so every registry.call issued from model code inside the
# mapped region (a) sees the mesh axis name for its declared completing
# collective and (b) ticks the tp route counters.  Like the route counters,
# this is a trace-time mechanism: jit caches replay it for free.
_TP_AXIS: Optional[str] = None


def tp_axis() -> Optional[str]:
    """The active mapped mesh axis name, or None outside a tp_scope."""
    return _TP_AXIS


@contextlib.contextmanager
def tp_scope(axis: str):
    """Mark the dynamic extent of tracing a shard_map'd serving region.

    Inside the scope, ops called with a ``tp=`` tag complete themselves
    with the collective their :class:`TPContract` declares over ``axis``;
    outside it, tags are inert annotations of the parallel structure."""
    global _TP_AXIS
    prev = _TP_AXIS
    _TP_AXIS = str(axis)
    try:
        yield
    finally:
        _TP_AXIS = prev


# ------------------------------------------------- dense-score tripwire
# Trace-time shape-assertion hook for reference attention lowerings:
# inside a ``forbid_dense_scores()`` scope, any path that would materialize
# a dense (Sq, Skv) score tensor raises instead of tracing.  Tests wrap a
# ``dispatch="kernels"`` train step in it to PROVE the fused routes carried
# the whole graph — counters say which route ran, the tripwire says no
# other route could have.
_forbid_dense = False


@contextlib.contextmanager
def forbid_dense_scores():
    global _forbid_dense
    prev = _forbid_dense
    _forbid_dense = True
    try:
        yield
    finally:
        _forbid_dense = prev


def assert_no_dense_scores(where: str, sq: int, skv: int) -> None:
    if _forbid_dense:
        raise AssertionError(
            f"dense ({sq}, {skv}) attention scores would be materialized "
            f"in {where} inside a forbid_dense_scores() scope")


# ------------------------------------------------------- the generic path
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _vjp_call(name: str, ctx: OpCtx, *args):
    return _REGISTRY[name].kernel(ctx, *args)


def _vjp_call_fwd(name: str, ctx: OpCtx, *args):
    spec = _REGISTRY[name]
    if spec.vjp_fwd is not None:
        return spec.vjp_fwd(ctx, *args)
    return spec.kernel(ctx, *args), args


def _vjp_call_bwd(name: str, ctx: OpCtx, res, g):
    return _REGISTRY[name].vjp_bwd(ctx, res, g)


_vjp_call.defvjp(_vjp_call_fwd, _vjp_call_bwd)


def _freeze(statics: Optional[Dict[str, Any]]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(sorted((statics or {}).items(), key=lambda kv: kv[0]))


def call(name: str, *args, statics: Optional[Dict[str, Any]] = None,
         mode: str = "auto", allow_kernels: bool = False,
         tp: Optional[str] = None):
    """Route one op call: the single code path behind every dispatch facade.

    ``mode`` is the fully-resolved policy ("kernels" | "reference" |
    "auto"); ``allow_kernels`` is the facade's combined policy + backend
    gate (``mode != "reference" and (mode == "kernels" or on-TPU)``).
    Eligibility, plan resolution, the level gate, and route counting are
    generic; everything op-specific lives in the OpSpec.

    ``tp`` names one of the op's declared :class:`TPContract` sharding
    contracts ("col" | "row" | "heads" | ...).  Inside a :func:`tp_scope`
    (i.e. while tracing a shard_map'd serving region) the contract's
    completing collective runs HERE, on the op's output — keeping
    registry.call the single routing path inside the mapped region.
    Outside a scope the tag is inert, so tagged model code is
    mesh-agnostic.
    """
    spec = get(name)
    if spec.reference is None:
        raise ValueError(f"op {name!r} has no dispatch surface "
                         "(tune-only registration)")
    st = _freeze(statics)
    st_dict = dict(st)
    use_kernel = (bool(allow_kernels) and spec.kernel is not None
                  and (spec.eligible is None
                       or spec.eligible(st_dict, *args)))
    level = Level.T3_REPLICATED
    plan_kw: Dict[str, Any] = {}
    source: Optional[str] = None
    if use_kernel and spec.plan_shape is not None:
        shape = spec.plan_shape(st_dict, *args)
        key_dtype = (spec.plan_dtype(st_dict, *args)
                     if spec.plan_dtype is not None else args[0].dtype)
        level, kw, source = resolve_plan_source(
            spec.plan_kernel or name, shape, key_dtype, level, "tuned")
        plan_kw = dict(kw or {})
        if level in (Level.T0_NAIVE, Level.T1_PIPELINED):
            # the tuned entry says the reference lowering wins here:
            # honor it under "auto" (and count the reference route,
            # tagged with the lookup source, so stats can't disagree
            # with lookup_stats); an explicit "kernels" policy forces
            # the Pallas lowering, keeping any tuned tile geometry
            if mode != "kernels":
                use_kernel = False
            else:
                level = Level.T3_REPLICATED
    route = "kernel" if use_kernel else "reference"
    count_route(spec.stats_op or name, route, source)
    ctx = OpCtx(op=name, mode=mode, level=int(level),
                plan=tuple(sorted(plan_kw.items())), statics=st)
    if use_kernel:
        if spec.vjp_bwd is not None:
            out = _vjp_call(name, ctx, *args)
        else:
            out = spec.kernel(ctx, *args)
    else:
        out = spec.reference(ctx, *args)
    if tp is not None and _TP_AXIS is not None:
        contract = (spec.tp or {}).get(tp)
        if contract is None:
            raise ValueError(
                f"op {name!r} declares no tp contract {tp!r} "
                f"(has: {sorted(spec.tp or {})}); sharded serving cannot "
                "complete this call inside the mapped region")
        # named, so that a profile or the HLO's op names show them
        if contract.collective == "psum":
            with jax.named_scope("tp.psum"):
                out = jax.lax.psum(out, _TP_AXIS)
        elif contract.collective == "all_gather":
            with jax.named_scope("tp.all_gather"):
                out = jax.lax.all_gather(out, _TP_AXIS,
                                         axis=contract.gather_axis,
                                         tiled=True)
    return out
