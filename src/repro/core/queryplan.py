"""Declarative query plans: ``QuerySpec`` → planner → fused executor.

The querying stage is one algorithm family (a similarity scan over the
hierarchical memory followed by a selection rule), but the legacy API
exposed it through four divergent entry points of which only the
sampling/AKR pair reached the fused cross-session device path. This
module unifies all of it behind three layers:

* **QuerySpec** — a declarative description of ONE query against ONE
  session: text or precomputed embedding, retrieval strategy name,
  budget, per-query ``tau``/``theta``/``beta`` overrides, and a seed
  policy (``seed=None`` consumes the session's PRNG chain exactly like
  the legacy paths; an explicit seed derives a detached key and leaves
  the chain untouched).
* **Planner** (``build_plan``) — groups compatible specs into
  ``ExecutionGroup``s (same strategy + resolved budget + scan/sampling
  parameters → one padded block) and emits an explicit ``QueryPlan``
  the caller can inspect before running anything.
* **Executor** (``execute_plan``) — runs ONE scan launch per group and
  dispatches vmapped per-strategy post-processing, so every registered
  strategy — not just sampling/AKR — gets the "one scan, zero host
  gathers" path. For sampling/AKR/top-k groups that one launch is the
  FUSED retrieval scan (``kops.fused_retrieve_stack``): the inverse-CDF
  draws, drawn probabilities, and top-k resolve inside the kernel
  epilogue, so no (S, Q, cap) score tensor crosses the launch boundary
  (AKR's stop rule then runs over the already-computed draw state — no
  re-scoring). BOLT/MDF/AKS (and uniform) genuinely consume dense
  scores/embeddings, so their groups keep the materialising
  ``stack.search`` launch; ``execute_plan(..., fused=False)`` forces
  that dense path for every strategy (results are draw-for-draw
  identical — the fused epilogue computes the same canonical chunked
  CDF over the same probabilities). With the manager's ``MemoryArena``
  (the default) the scan operand IS the arena's grow-in-place
  super-buffers: every group scans all arena SLOTS in slot order (lanes
  without queries are padding, freed slots of closed sessions are
  ``None`` hole lanes whose ``(0, 0)`` windows mask them out — per-lane
  math is independent, so the queried lanes are bit-identical to a
  subset scan) and NO ingest↔query interleaving, close, or slot reuse
  ever restacks device buffers
  (``manager.io_stats["stack_rebuilds"]`` stays 0). The scan's
  ``valid`` operand is the arena's ``(S, 2)`` ``(start, size)`` window
  array — a session under sliding-window eviction is a device-side
  ring, so validity wraps; masks derive on device. Detached managers
  fall back to the per-group version-cached ``MemoryStack``.

Strategies live in a registry (``register_strategy`` / ``get_strategy``)
wrapping every selection rule in ``repro.core.retrieval`` behind a
common batched interface over ``(S, Q, cap)`` scan outputs. Each
strategy declares how its draws expand to raw frame ids:

* ``members`` — through the cluster member reservoirs, fused with the
  sampling itself into one jit'd device program (sampling, AKR);
* ``index``  — draws are memory slots mapped to their centroid frame id
  via the device-resident index_frame table (top-k, BOLT, MDF, AKS);
* ``raw``    — draws already are frame ids (uniform).

PRNG discipline: within a group, sessions are visited in sorted-sid
order and each session's chain advances by exactly its own chain-policy
query count (padding lanes consume dummy keys), so every legacy entry
point shimmed over this module stays draw-for-draw identical to its
pre-redesign output — see tests/test_crosssession.py and
tests/test_queryplan.py.

Ownership/staleness at this layer: the executor owns NOTHING — it
borrows device views (arena super-buffers or cached stacks) from the
manager per group, inside one call, and never caches them across
calls. That is what makes it safe against the arena's donation rule
(any ingest tick invalidates previously returned handles): each group
re-reads its views after the point where ticks could have run.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import (Callable, Dict, List, Mapping, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import retrieval as rt
from repro.core import tiering
from repro.core.memory import VenusMemory, expand_gather
from repro.kernels import ops as kops
from repro.obs import span


# ---------------------------------------------------------------------------
# Specs and plans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class QuerySpec:
    """One query against one session, declaratively.

    ``budget`` means "draw count" for sampling/uniform/BOLT/MDF/AKS,
    "k" for top-k, and "n_max" for AKR; ``None`` falls back to the
    manager config (``cfg.n_max``). ``tau``/``theta``/``beta`` override
    the config per query (``tau`` feeds both the scan softmax and
    BOLT's inverse-transform CDF). ``seed=None`` = chain policy (consume
    the session PRNG chain); an int detaches the query from the chain.
    """
    sid: int
    text: Optional[str] = None
    embedding: Optional[np.ndarray] = None
    strategy: str = "akr"
    budget: Optional[int] = None
    tau: Optional[float] = None
    theta: Optional[float] = None
    beta: Optional[float] = None
    seed: Optional[int] = None


class GroupKey(NamedTuple):
    """Resolved compatibility key: specs sharing it run as one block."""
    strategy: str
    budget: int
    tau: float
    theta: float
    beta: float


@dataclass
class ExecutionGroup:
    """One padded execution block: ONE fused scan answers every spec."""
    strategy: "RetrievalStrategy"
    key: GroupKey
    indices: List[int] = field(default_factory=list)   # spec positions
    order: Dict[int, List[int]] = field(default_factory=dict)

    @property
    def sids(self) -> Tuple[int, ...]:
        return tuple(sorted(self.order))

    @property
    def qmax(self) -> int:
        return max(len(v) for v in self.order.values())

    def describe(self) -> str:
        k = self.key
        return (f"{k.strategy}(budget={k.budget}, tau={k.tau:g}, "
                f"theta={k.theta:g}, beta={k.beta:g}) "
                f"sessions={list(self.sids)} queries={len(self.indices)}")


@dataclass
class QueryPlan:
    """The planner's output: inspectable before (or instead of) running.
    ``tick`` and ``rids`` label its spans: the manager's query-tick
    number and the specs' request ids (``SessionManager.plan``)."""
    specs: List[QuerySpec]
    groups: List[ExecutionGroup]
    tick: int = 0
    rids: Tuple[int, ...] = ()

    @property
    def n_scans(self) -> int:
        """Fused scan launches this plan will cost — one per group."""
        return len(self.groups)

    def describe(self) -> str:
        lines = [f"QueryPlan: {len(self.specs)} specs -> "
                 f"{len(self.groups)} groups ({self.n_scans} scans)"]
        lines += [f"  group {i}: {g.describe()}"
                  for i, g in enumerate(self.groups)]
        return "\n".join(lines)


def build_plan(specs: Sequence[QuerySpec], cfg,
               sessions: Optional[Mapping[int, object]] = None, *,
               standing: bool = False) -> QueryPlan:
    """Group compatible specs into execution groups.

    ``cfg`` supplies the ``tau``/``theta``/``beta``/``n_max`` defaults
    (any object with those attributes — ``VenusConfig`` in practice).
    Groups are emitted in first-spec-appearance order; within a group,
    sessions run in sorted-sid order and each session's queries keep
    arrival order (the order its PRNG chain is consumed in).

    When ``sessions`` (sid → session state) is provided — the
    ``SessionManager.plan`` path — the planner also validates strategy
    ↔ session compatibility at PLAN time: the ``uniform`` strategy
    draws arbitrary archive frame ids, so against a window-evicting
    session whose ``FrameStore`` has no spill tier it is rejected here
    with a clear error instead of the deep ``IndexError`` the read
    would otherwise hit. With spill enabled the trimmed frames fault
    back from disk, so ``uniform`` is legal again and no check fires.

    ``standing=True`` is the validation mode the standing-query
    registry runs at registration time (``core.standing``): the spec
    must resolve — with the SAME GroupKey resolution as an ad-hoc plan,
    which is what keeps the differential bit-identity claim honest —
    but under the ingest-path evaluation contract: a deterministic
    strategy the fused kernel epilogue computes in-launch (``topk``;
    the stochastic strategies would consume the session PRNG chain
    from inside ingest ticks, silently perturbing every subsequent
    ad-hoc query) and no explicit ``seed`` (standing evaluation never
    draws, so a seed could only signal a misunderstanding).
    """
    specs = list(specs)
    groups: Dict[GroupKey, ExecutionGroup] = {}
    for j, spec in enumerate(specs):
        if spec.text is None and spec.embedding is None:
            raise ValueError(f"spec {j}: needs text or embedding")
        strat = get_strategy(spec.strategy)
        if standing:
            if strat.stochastic or strat.name not in _FUSED_STRATEGIES:
                raise ValueError(
                    f"spec {j}: strategy {strat.name!r} cannot run as a "
                    f"standing query — the ingest-path evaluation is "
                    f"deterministic and resolves inside the fused "
                    f"launch, so only non-stochastic fused strategies "
                    f"('topk') are accepted (stochastic strategies "
                    f"would consume the session PRNG chain per ingest "
                    f"tick)")
            if spec.seed is not None:
                raise ValueError(
                    f"spec {j}: standing queries never draw, so an "
                    f"explicit seed has no effect — pass seed=None")
        if strat.name == "uniform" and sessions is not None:
            st = sessions.get(int(spec.sid))
            policy = (st.memory.eviction.name if st is not None
                      else "none")
            if (st is not None and policy != "none"
                    and not st.frames.spill_enabled):
                raise ValueError(
                    f"spec {j}: strategy 'uniform' draws arbitrary "
                    f"archive frame ids, but session {spec.sid} evicts "
                    f"with policy '{policy}' and has no spill tier — "
                    f"its trimmed frames are deleted, so uniform reads "
                    f"would IndexError in FrameStore.get. Use a "
                    f"members-expanding strategy, keep the session on "
                    f"eviction='none', or set VenusConfig(spill_dir=..."
                    f") so trimmed frames demote to disk and fault "
                    f"back in.")
        key = GroupKey(
            strategy=strat.name,
            budget=int(spec.budget if spec.budget is not None
                       else cfg.n_max),
            tau=float(spec.tau if spec.tau is not None else cfg.tau),
            theta=float(spec.theta if spec.theta is not None
                        else cfg.theta),
            beta=float(spec.beta if spec.beta is not None else cfg.beta))
        g = groups.get(key)
        if g is None:
            g = groups[key] = ExecutionGroup(strategy=strat, key=key)
        g.indices.append(j)
        g.order.setdefault(int(spec.sid), []).append(j)
    return QueryPlan(specs=specs, groups=list(groups.values()))


# ---------------------------------------------------------------------------
# Strategy registry: every retrieval.py selection rule, batched
# ---------------------------------------------------------------------------


class StrategyContext(NamedTuple):
    """Everything a strategy may post-process after the ONE fused scan."""
    sims: jnp.ndarray             # (S, Q, cap) cosine similarities
    probs: jnp.ndarray            # (S, Q, cap) temperature softmax
    valid: jnp.ndarray            # (S, cap) per-session slot validity
    emb: jnp.ndarray              # (S, cap, d) index embedding stack
    keys: Optional[jnp.ndarray]   # (S, Q) PRNG keys (stochastic only)
    total_frames: np.ndarray      # (S,) raw frames seen per session
    key: GroupKey                 # resolved strategy/budget/params
    qcount: np.ndarray            # (S,) real (non-padding) queries


class StrategyOutput(NamedTuple):
    draws: jnp.ndarray            # (S, Q, n) int32 — see strategy.expand
    valid: jnp.ndarray            # (S, Q, n) bool — slot actually drawn
    n_drawn: np.ndarray           # (S, Q) int
    mass: np.ndarray              # (S, Q) float (nan if undefined)


@dataclass(frozen=True)
class RetrievalStrategy:
    """A retrieval rule behind the common batched interface.

    ``run`` post-processes the scan outputs into draws; ``run_expand``
    (members strategies only) fuses selection + reservoir expansion into
    one jit'd device program, returning ``(output, frame_ids, ok)``.
    """
    name: str
    stochastic: bool              # consumes the session PRNG chain
    expand: str                   # "members" | "index" | "raw"
    run: Callable[[StrategyContext], StrategyOutput]
    run_expand: Optional[Callable] = None

    def __post_init__(self):
        assert self.expand in ("members", "index", "raw"), self.expand
        assert (self.run_expand is not None) == (self.expand == "members")


_REGISTRY: Dict[str, RetrievalStrategy] = {}


def register_strategy(strategy: RetrievalStrategy) -> RetrievalStrategy:
    assert strategy.name not in _REGISTRY, strategy.name
    _REGISTRY[strategy.name] = strategy
    return strategy


def get_strategy(name: str) -> RetrievalStrategy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown retrieval strategy {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# --- Venus sampling / AKR (expand through member reservoirs) ---------------


@functools.partial(jax.jit, static_argnames=("theta", "beta", "n_max"))
def _fused_akr_expand(probs, keys, members, counts, u, *, theta, beta,
                      n_max):
    """probs (S,Q,cap) + keys (S,Q) → AKR draws (S,Q,n_max) → member
    frame ids (S,Q,n_max), all in one program: the reservoir gather runs
    on the device-resident members stack, so nothing round-trips to host
    between sampling and expansion. Each (s, q) lane is bitwise the
    scalar ``akr_progressive`` + ``expand_draws`` chain for that key."""
    akr = jax.vmap(lambda p, k: rt.akr_progressive_batch(
        p, k, theta=theta, beta=beta, n_max=n_max))(probs, keys)
    fids, ok = jax.vmap(lambda m, c, d, v: expand_gather(m, c, d, v, u))(
        members, counts, akr.draws, akr.valid)
    return akr, fids, ok


@functools.partial(jax.jit, static_argnames=("n",))
def _fused_sample_expand(probs, keys, members, counts, u, *, n):
    """Fixed-budget variant: n draws per lane, every slot valid."""
    draws, _ = jax.vmap(lambda p, k: rt.sampling_retrieve_batch(
        p, k, n))(probs, keys)
    valid = jnp.ones(draws.shape, bool)
    fids, ok = jax.vmap(lambda m, c, d, v: expand_gather(m, c, d, v, u))(
        members, counts, draws, valid)
    return draws, fids, ok


def _run_sampling(ctx: StrategyContext) -> StrategyOutput:
    n = ctx.key.budget
    draws, _ = jax.vmap(lambda p, k: rt.sampling_retrieve_batch(
        p, k, n))(ctx.probs, ctx.keys)
    sq = draws.shape[:2]
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full(sq, n), np.full(sq, np.nan))


def _run_expand_sampling(ctx: StrategyContext, members, counts, u):
    draws, fids, ok = _fused_sample_expand(ctx.probs, ctx.keys, members,
                                           counts, u, n=ctx.key.budget)
    sq = draws.shape[:2]
    out = StrategyOutput(draws, jnp.ones(draws.shape, bool),
                         np.full(sq, ctx.key.budget), np.full(sq, np.nan))
    return out, fids, ok


def _run_akr(ctx: StrategyContext) -> StrategyOutput:
    k = ctx.key
    akr = jax.vmap(lambda p, kk: rt.akr_progressive_batch(
        p, kk, theta=k.theta, beta=k.beta, n_max=k.budget))(
            ctx.probs, ctx.keys)
    return StrategyOutput(akr.draws, akr.valid, np.asarray(akr.n_drawn),
                          np.asarray(akr.mass))


def _run_expand_akr(ctx: StrategyContext, members, counts, u):
    k = ctx.key
    akr, fids, ok = _fused_akr_expand(ctx.probs, ctx.keys, members,
                                      counts, u, theta=k.theta,
                                      beta=k.beta, n_max=k.budget)
    out = StrategyOutput(akr.draws, akr.valid, np.asarray(akr.n_drawn),
                         np.asarray(akr.mass))
    return out, fids, ok


# --- baselines (expand via the index_frame table, or raw frame ids) --------


def _run_topk(ctx: StrategyContext) -> StrategyOutput:
    k = ctx.key.budget
    draws = rt.topk_retrieve_batch(ctx.sims, ctx.valid, k)
    sq = draws.shape[:2]
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full(sq, k), np.full(sq, np.nan))


def _run_uniform(ctx: StrategyContext) -> StrategyOutput:
    n = ctx.key.budget
    per_s = rt.uniform_retrieve_batch(
        jnp.asarray(ctx.total_frames, jnp.int32), n)      # (S, n)
    s, q = ctx.sims.shape[:2]
    draws = jnp.broadcast_to(per_s[:, None, :], (s, q, n))
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full((s, q), n), np.full((s, q), np.nan))


def _run_bolt(ctx: StrategyContext) -> StrategyOutput:
    n = ctx.key.budget
    draws = rt.bolt_inverse_transform_batch(ctx.sims, ctx.valid, n,
                                            tau=ctx.key.tau)
    sq = draws.shape[:2]
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full(sq, n), np.full(sq, np.nan))


def _run_mdf(ctx: StrategyContext) -> StrategyOutput:
    n = ctx.key.budget
    per_s = rt.mdf_retrieve_batch(ctx.emb, ctx.valid, n)  # (S, n)
    s, q = ctx.sims.shape[:2]
    draws = jnp.broadcast_to(per_s[:, None, :], (s, q, n))
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full((s, q), n), np.full((s, q), np.nan))


def _run_aks(ctx: StrategyContext) -> StrategyOutput:
    """AKS's recursive budget split concretises per-region masses, so
    its post-processing is host-driven — the group still costs only the
    ONE fused scan; padding lanes are skipped entirely."""
    n = ctx.key.budget
    s, q = ctx.sims.shape[:2]
    rows = np.zeros((s, q, n), np.int32)
    for si in range(s):
        for qi in range(int(ctx.qcount[si])):
            rows[si, qi] = np.asarray(rt.aks_retrieve(
                ctx.sims[si, qi], ctx.valid[si], n))
    draws = jnp.asarray(rows)
    return StrategyOutput(draws, jnp.ones(draws.shape, bool),
                          np.full((s, q), n), np.full((s, q), np.nan))


register_strategy(RetrievalStrategy(
    "sampling", stochastic=True, expand="members",
    run=_run_sampling, run_expand=_run_expand_sampling))
register_strategy(RetrievalStrategy(
    "akr", stochastic=True, expand="members",
    run=_run_akr, run_expand=_run_expand_akr))
register_strategy(RetrievalStrategy(
    "topk", stochastic=False, expand="index", run=_run_topk))
register_strategy(RetrievalStrategy(
    "uniform", stochastic=False, expand="raw", run=_run_uniform))
register_strategy(RetrievalStrategy(
    "bolt", stochastic=False, expand="index", run=_run_bolt))
register_strategy(RetrievalStrategy(
    "mdf", stochastic=False, expand="index", run=_run_mdf))
register_strategy(RetrievalStrategy(
    "aks", stochastic=False, expand="index", run=_run_aks))


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


@dataclass
class QueryResult:
    frame_ids: np.ndarray          # selected raw-frame ids (deduped for
    #                                members strategies; rank/time order
    #                                preserved for the baselines)
    draws: np.ndarray              # index draws (or frame ids for "raw")
    n_drawn: int
    mass: float
    # host seconds, read off the tick's spans: ``embed_query`` the
    # tick's text-tower time (``venus.execute.embed_text``), the same in
    # every group's results; ``keys`` the group's PRNG keys
    # (``venus.execute.keys``); ``similarity`` the launch of the group's
    # scan (``venus.execute.scan``; the scan's device time is in the
    # trace); ``sample_expand`` post-processing, expansion and the host
    # reads of the results (``venus.execute.expand``), which includes
    # waiting for the scan; under a sharded arena also ``shard_gather``,
    # the part of it that reads the answers back from every chip
    # (``venus.execute.shard_gather``)
    timings: Dict[str, float]


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


@jax.jit
def _gather_index_frames(table: jnp.ndarray, draws: jnp.ndarray
                         ) -> jnp.ndarray:
    """table (S, cap) index_frame ids; draws (S, Q, n) slots → frame
    ids (S, Q, n), all on device."""
    cap = table.shape[1]
    return jax.vmap(lambda t, d: t[jnp.clip(d, 0, cap - 1)])(table, draws)


# --- fused-epilogue routing -------------------------------------------------
#
# Strategies whose selection rule the fused kernel epilogue computes
# in-launch: sampling and AKR consume the inverse-CDF draws (+ drawn
# probabilities for AKR's stop rule), top-k consumes the running top-k.
# Everything else (BOLT's CDF over ALL lanes, MDF's embedding scan, AKS's
# host-driven region split, uniform's no-scan rule) takes the dense path.
_FUSED_STRATEGIES = ("sampling", "akr", "topk")


@functools.partial(jax.jit, static_argnames=("n",))
def _targets_from_keys(keys: jnp.ndarray, *, n: int) -> jnp.ndarray:
    """keys (S, Q) → inverse-CDF draw targets (S, Q, n). Each lane is
    exactly ``draw_targets(key, n)`` — the one variate block the direct
    ``sampling_retrieve``/``akr_progressive`` call consumes per key, so
    fused and direct draws see identical targets."""
    return jax.vmap(jax.vmap(lambda k: rt.draw_targets(k, n)))(keys)


@jax.jit
def _expand_stack(members, counts, draws, valid, u):
    """Stacked reservoir expansion of already-computed draws (the fused
    path's counterpart of ``_fused_sample_expand`` — sampling happened
    in the kernel, only the gather remains)."""
    fids, ok = jax.vmap(lambda m, c, d, v: expand_gather(m, c, d, v, u))(
        members, counts, draws, valid)
    return fids, ok


@functools.partial(jax.jit, static_argnames=("theta", "beta", "n_max"))
def _fused_akr_post(draws, drawn_p, p_max, members, counts, u, *, theta,
                    beta, n_max):
    """AKR over the fused kernel's outputs: the Eq. 6/7 stop rule runs
    on the in-launch draw state (draws + crossing-lane probabilities +
    p_max = 1/l) — no re-scoring, no (S, Q, cap) tensor — then the
    reservoir gather expands the surviving draws, all in one program.
    Each (s, q) lane stops bit-identically to ``akr_progressive`` over
    that lane's materialised probabilities."""
    akr = jax.vmap(jax.vmap(lambda d, p, pm: rt.akr_from_draws(
        d, p, pm, theta=theta, beta=beta, n_max=n_max)))(
            draws, drawn_p, p_max)
    fids, ok = jax.vmap(lambda m, c, d, v: expand_gather(m, c, d, v, u))(
        members, counts, akr.draws, akr.valid)
    return akr, fids, ok


def execute_plan(manager, plan: QueryPlan, *, fused: bool = True,
                 coarse: bool = True) -> List[QueryResult]:
    """Run every group of the plan: ONE scan launch per group (the fused
    retrieval scan for sampling/AKR/top-k groups when ``fused``, the
    dense ``similarity_scan_stack`` otherwise), vmapped strategy
    post-processing, device-side expansion. Returns results in the
    plan's spec order.

    ``coarse`` is the two-stage escape hatch: when True (default) and
    the arena's hierarchical tier holds at least one consolidated
    summary row, fused groups run coarse-scan → winner-block-gather →
    candidate-scan (``tiering.two_stage_retrieve``) instead of the flat
    capacity scan. Until the first consolidation — and always with
    ``coarse=False`` — the flat path runs UNCHANGED (bit-identical to a
    coarse-less build); PRNG chains advance identically either way."""
    specs = plan.specs
    results: List[Optional[QueryResult]] = [None] * len(specs)
    missing = [j for j, s in enumerate(specs) if s.embedding is None]
    embedded: Dict[int, np.ndarray] = {}
    with span("execute.embed_text") as emb:
        if missing:
            embs = manager.embedder.embed_queries(
                [specs[j].text for j in missing])
            embedded = {j: np.asarray(embs[i], np.float32)
                        for i, j in enumerate(missing)}
    t_embed = emb.seconds
    for group in plan.groups:
        _execute_group(manager, group, specs, embedded, results, t_embed,
                       fused=fused, coarse=coarse)
    return results


def _spec_embedding(spec: QuerySpec, j: int, embedded) -> np.ndarray:
    return (np.asarray(spec.embedding, np.float32)
            if spec.embedding is not None else embedded[j])


def _group_keys(manager, group: ExecutionGroup, specs, qmax, lanes
                ) -> Optional[jnp.ndarray]:
    """Per-lane key rows (L, qmax) over the scan's lane order.
    Chain-policy lanes consume the session PRNG chain in arrival order —
    exactly the subkeys the same queries would have drawn through the
    legacy paths; explicit-seed lanes derive detached keys; padding
    lanes (whole sessions the group doesn't target, and ``None`` hole
    lanes over freed arena slots) get dummy keys and leave their chains
    untouched."""
    if not group.strategy.stochastic:
        return None
    key_rows = []
    for sid in lanes:
        idxs = group.order.get(sid, ())
        n_chain = sum(1 for j in idxs if specs[j].seed is None)
        chain = (manager.sessions[sid].next_keys(n_chain)
                 if n_chain else None)
        ks, ci = [], 0
        for j in idxs:
            if specs[j].seed is None:
                ks.append(chain[ci])
                ci += 1
            else:
                ks.append(jax.random.key(int(specs[j].seed)))
        if len(ks) < qmax:
            ks.extend(list(jax.random.split(jax.random.key(0),
                                            qmax - len(ks))))
        key_rows.append(jnp.stack(ks))
    return jnp.stack(key_rows)


def _to_host(arrays, sharded: bool, timings: Dict[str, float]) -> list:
    """A group's answers as numpy arrays. Under a sharded arena they
    come back from every chip: they are awaited first, so that the
    ``execute.shard_gather`` span times the readback alone, and its
    seconds go into ``timings["shard_gather"]``."""
    if not sharded:
        return [np.asarray(x) for x in arrays]
    jax.block_until_ready(arrays)
    with span("execute.shard_gather") as sp:
        out = [np.asarray(x) for x in arrays]
    timings["shard_gather"] = sp.seconds
    return out


def _execute_group(manager, group: ExecutionGroup, specs, embedded,
                   results, t_embed: float, *, fused: bool = True,
                   coarse: bool = True) -> None:
    # scan-lane order: arena mode scans EVERY slot in slot order (the
    # super-buffers are consumed as-is — zero restacks; freed slots are
    # None hole lanes, masked out by their (0, 0) windows); detached
    # mode scans exactly the group's sessions via the version-cached
    # stack
    lanes = manager.scan_lanes(group.sids)
    k = group.key
    with span("execute.group", strategy=k.strategy, budget=k.budget,
              lanes=len(lanes), qmax=group.qmax):
        _run_group(manager, group, lanes, specs, embedded, results,
                   t_embed, fused=fused, coarse=coarse)


def _run_group(manager, group: ExecutionGroup, lanes, specs, embedded,
               results, t_embed: float, *, fused: bool,
               coarse: bool) -> None:
    cfg = manager.cfg
    strat = group.strategy
    use_fused = fused and strat.name in _FUSED_STRATEGIES
    sids = group.sids
    lane_of = {sid: si for si, sid in enumerate(lanes)
               if sid is not None}
    ln, qmax = len(lanes), group.qmax
    timings: Dict[str, float] = {"embed_query": t_embed}

    q_stack = np.zeros((ln, qmax, manager.embed_dim), np.float32)
    qcount = np.zeros((ln,), np.int32)
    for sid in sids:
        si = lane_of[sid]
        idxs = group.order[sid]
        qcount[si] = len(idxs)
        for qi, j in enumerate(idxs):
            q_stack[si, qi] = _spec_embedding(specs[j], j, embedded)
    with span("execute.keys") as sp:
        keys = _group_keys(manager, group, specs, qmax, lanes)
    timings["keys"] = sp.seconds

    # --- the ONE scan launch for this group ------------------------------
    with span("execute.scan") as sp:
        stack = manager.memory_stack(lanes)
        a = stack.arena_view()
        sharded = a is not None and a.n_shards > 1
        if sharded:
            gathered = kops.scan_counts()["shard_gather_bytes"]
        k = group.key
        # two-stage trigger: fused group + arena-backed + the hierarchical
        # tier actually holds consolidated rows (before that the coarse
        # tier adds nothing the flat scan doesn't cover — and skipping it
        # keeps the pre-consolidation path bit-identical to a coarse-less
        # build, which is the `coarse=False` contract too)
        two_stage = (use_fused and coarse and a is not None
                     and a.has_consolidated())
        ts = None
        if use_fused:
            # fused path: draws/top-k resolve inside the launch; dense
            # (S, Q, cap) scores never cross the kernel boundary. Targets
            # derive from the SAME keys in both modes, so session PRNG
            # chains advance identically with or without the coarse tier.
            if strat.stochastic:
                targets = _targets_from_keys(keys, n=k.budget)
            else:           # top-k ignores the draw epilogue: dummy targets
                targets = jnp.zeros((ln, qmax, 1), jnp.float32)
            n_topk = k.budget if strat.name == "topk" else 1
            if two_stage:
                ts = tiering.two_stage_retrieve(
                    a, jnp.asarray(q_stack), targets, tau=k.tau,
                    n_topk=n_topk, topb=getattr(cfg, "coarse_topb", 4))
                fr = ts.fr
                manager.io_stats["two_stage_groups"] += 1
            else:
                fr = stack.fused_retrieve(
                    jnp.asarray(q_stack), targets, tau=k.tau, n_topk=n_topk)
        else:
            sims, probs = stack.search(jnp.asarray(q_stack), tau=k.tau)
        if len(sids) == 1:   # single-session group: per-session accounting
            manager.io_stats["scans"] += 1
            manager.sessions[sids[0]].memory.io_stats["scans"] += 1
        else:
            manager.io_stats["fused_scans"] += 1
        manager.io_stats["group_scans"] += 1
        if sharded:
            # this launch fanned out per shard under shard_map (the kernel
            # entries count bytes; this counts launches at the plan level)
            manager.io_stats["sharded_group_scans"] += 1
            manager.io_stats["shard_gather_bytes"] += (
                kops.scan_counts()["shard_gather_bytes"] - gathered)
    timings["similarity"] = sp.seconds

    # --- strategy post-processing + expansion ----------------------------
    with span("execute.expand") as sp:
        if use_fused:
            if strat.name == "topk":
                draws = fr.topk_i
                sq = draws.shape[:2]
                if ts is not None:
                    # candidate-local draws → candidate ifr; k may be
                    # clamped to the candidate width, and lanes can hold
                    # fewer valid candidates than k (a consolidated winner
                    # is ONE candidate), so masked slots — recognisable by
                    # their NEG_INF running-top-k score — are dropped
                    # rather than surfacing garbage frame ids
                    valid_d = fr.topk_v > -1e29
                    out = StrategyOutput(draws, valid_d, valid_d.sum(-1),
                                         np.full(sq, np.nan))
                    fids = tiering.gather_candidate_ifr(ts.cand_ifr,
                                                        out.draws)
                else:
                    out = StrategyOutput(draws, jnp.ones(draws.shape, bool),
                                         np.full(sq, draws.shape[-1]),
                                         np.full(sq, np.nan))
                    fids = _gather_index_frames(
                        stack.device_index_frames(), out.draws)
                ok = out.valid
            else:
                u = jnp.asarray(VenusMemory.expand_u(cfg.seed, k.budget),
                                jnp.int32)
                if ts is not None:
                    # candidate-local expansion: draws index the gathered
                    # (S, Q, C) candidate tables, whose member reservoirs
                    # came along in the stage-2 gather
                    if strat.name == "sampling":
                        valid_d = jnp.ones(fr.draws.shape, bool)
                        fids, ok = tiering.expand_candidates(
                            ts.cand_members, ts.cand_counts, fr.draws,
                            valid_d, u)
                        sq = fr.draws.shape[:2]
                        out = StrategyOutput(fr.draws, valid_d,
                                             np.full(sq, k.budget),
                                             np.full(sq, np.nan))
                    else:                                           # akr
                        akr, fids, ok = tiering.akr_post_candidates(
                            fr.draws, fr.drawn_p, fr.p_max[..., 0],
                            ts.cand_members, ts.cand_counts, u,
                            theta=k.theta, beta=k.beta, n_max=k.budget)
                        out = StrategyOutput(akr.draws, akr.valid,
                                             akr.n_drawn, akr.mass)
                else:
                    members, counts = stack.device_members()
                    if strat.name == "sampling":
                        valid_d = jnp.ones(fr.draws.shape, bool)
                        fids, ok = _expand_stack(members, counts, fr.draws,
                                                 valid_d, u)
                        sq = fr.draws.shape[:2]
                        out = StrategyOutput(fr.draws, valid_d,
                                             np.full(sq, k.budget),
                                             np.full(sq, np.nan))
                    else:                                           # akr
                        akr, fids, ok = _fused_akr_post(
                            fr.draws, fr.drawn_p, fr.p_max[..., 0], members,
                            counts, u, theta=k.theta, beta=k.beta,
                            n_max=k.budget)
                        out = StrategyOutput(akr.draws, akr.valid,
                                             akr.n_drawn, akr.mass)
                manager.io_stats["device_expands"] += 1
        else:
            emb_stack, valid = stack.device_stack()
            ctx = StrategyContext(
                sims=sims, probs=probs, valid=valid, emb=emb_stack, keys=keys,
                total_frames=np.asarray(
                    [manager.sessions[s].stats["frames_seen"]
                     if s is not None else 0 for s in lanes], np.int64),
                key=group.key, qcount=qcount)

            if strat.expand == "members":
                members, counts = stack.device_members()
                u = jnp.asarray(VenusMemory.expand_u(cfg.seed, k.budget),
                                jnp.int32)
                out, fids, ok = strat.run_expand(ctx, members, counts, u)
                manager.io_stats["device_expands"] += 1
            else:
                out = strat.run(ctx)
                ok = out.valid
                if strat.expand == "index":
                    fids = _gather_index_frames(
                        stack.device_index_frames(), out.draws)
                else:                               # raw: draws ARE frame ids
                    fids = out.draws
        fids_np, ok_np, draws_np, n_drawn, mass = _to_host(
            (fids, ok, out.draws, out.n_drawn, out.mass), sharded, timings)
    timings["sample_expand"] = sp.seconds

    for sid in sids:
        si = lane_of[sid]
        for qi, j in enumerate(group.order[sid]):
            lane = fids_np[si, qi][ok_np[si, qi]].astype(np.int64)
            if strat.expand == "members":       # reservoir picks: dedup
                lane = np.unique(lane)
            results[j] = QueryResult(
                frame_ids=lane, draws=draws_np[si, qi],
                n_drawn=int(n_drawn[si, qi]), mass=float(mass[si, qi]),
                timings=dict(timings))
