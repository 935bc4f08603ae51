"""One-launch fused retrieval + int8 quantised index (PR-6 tentpole).

Three layers of guarantees:

* **Kernel**: ``fused_retrieve_stack`` (draws + drawn probabilities +
  top-k + softmax stats in one launch) matches the materialised
  two-launch path draw-for-draw on both backends, across the edge
  shapes that historically break scan kernels — size-0 sessions,
  ``(start, size)`` ring windows that wrap, capacities that don't
  divide the block size, S == 1, capacity < DRAW_BLK. Integer outputs
  (draws, top-k indices) are bitwise-exact everywhere; on the default
  jnp backend the float by-products are bitwise too (shared
  materialisation), while the Pallas kernel's in-register recompute of
  p = exp(s/τ − m)/l may differ from a separate launch's epilogue by a
  few ulps (different XLA programs contract the chain differently), so
  drawn_p/p_max get allclose there.
* **Contract**: no O(S·Q·cap) output — a ``lower()``/``cost_analysis``
  guard pins the launch-boundary contract the bandwidth win rests on.
* **System**: the plan executor routes sampling/AKR/top-k through the
  fused launch (``fused_draw_launches``) with BOLT et al. falling back
  to dense scores, ``fused=False`` forces dense with identical results,
  and the int8 arena quantises at the append scatter, streams 4× fewer
  bytes per scan, and keeps top-k recall within drift bounds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import retrieval as rt
from repro.core.memory import quantise_rows
from repro.core.queryplan import QuerySpec
from repro.core.session import SessionManager, VenusConfig
from repro.data.video import OracleEmbedder, PixelEmbedder, VideoWorld, \
    WorldConfig
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.draws import (DRAW_BLK, blockwise_cdf,
                                  categorical_from_targets, chunk_cdf,
                                  draw_targets)


@pytest.fixture(params=["jnp", "pallas"])
def backend(request):
    old = kops.backend()
    kops.set_backend(request.param)
    yield request.param
    kops.set_backend(old)


def _case(S, Q, N, d, T, K, valid_kind, seed, sizes=None, wins=None,
          dtype="float32"):
    ks = jax.random.split(jax.random.key(seed), 4)
    query = jax.random.normal(ks[0], (S, Q, d))
    index = jax.random.normal(ks[1], (S, N, d))
    if dtype == "int8":
        index = jnp.asarray(np.stack(
            [quantise_rows(np.asarray(index[s]))[0] for s in range(S)]))
    if valid_kind == "sizes":
        valid = jnp.asarray(sizes, jnp.int32)
    elif valid_kind == "wins":
        valid = jnp.asarray(wins, jnp.int32)
    else:
        valid = jax.random.uniform(ks[2], (S, N)) < 0.7
    tkeys = jax.random.split(ks[3], S * Q)
    targets = jnp.stack([draw_targets(k, T) for k in tkeys]
                        ).reshape(S, Q, T)
    return query, index, valid, targets


# the edge shapes: size-0 session, S==1, cap % DRAW_BLK != 0, ring
# window wrapping around capacity, cap < DRAW_BLK, int8 index rows
CASES = [
    dict(S=3, Q=2, N=512, d=32, T=8, K=4, valid_kind="mask", seed=0),
    dict(S=1, Q=1, N=200, d=16, T=6, K=3, valid_kind="sizes", seed=1,
         sizes=[0]),
    dict(S=3, Q=2, N=700, d=16, T=6, K=3, valid_kind="sizes", seed=2,
         sizes=[0, 700, 123]),
    dict(S=2, Q=2, N=300, d=16, T=5, K=2, valid_kind="wins", seed=3,
         wins=[[250, 120], [0, 300]]),
    dict(S=2, Q=1, N=100, d=8, T=4, K=2, valid_kind="mask", seed=4),
    dict(S=2, Q=2, N=512, d=32, T=8, K=4, valid_kind="mask", seed=5,
         dtype="int8"),
    # several scan blocks: the CDF carry and the running top-k cross
    # block boundaries (2500 rows -> three 1024-row blocks)
    dict(S=2, Q=1, N=2500, d=16, T=8, K=3, valid_kind="sizes", seed=6,
         sizes=[2500, 1700]),
]


@pytest.mark.parametrize("case", CASES,
                         ids=[f"case{i}" for i in range(len(CASES))])
def test_fused_matches_materialised(backend, case):
    """Fused draws/top-k == the materialised scan + canonical chunked
    inverse-CDF + lax.top_k, per (s, q) lane, within one backend."""
    case = dict(case)
    tau, K = 0.1, case["K"]
    query, index, valid, targets = _case(**case)
    S, Q, N = case["S"], case["Q"], case["N"]

    fused = kops.fused_retrieve_stack(query, index, tau=tau, valid=valid,
                                      targets=targets, n_topk=K)
    sims, probs = kops.similarity_stack(query, index, tau=tau,
                                        valid=valid)
    vmask = ref.as_valid_mask(valid, N)
    for s in range(S):
        for q in range(Q):
            p0 = probs[s, q]
            draws = categorical_from_targets(p0, targets[s, q])
            np.testing.assert_array_equal(
                np.asarray(fused.draws[s, q]), np.asarray(draws))
            np.testing.assert_array_equal(
                np.asarray(fused.topk_i[s, q]),
                np.asarray(rt.topk_retrieve(sims[s, q], vmask[s], K)))
            dp = p0[draws]
            if backend == "jnp":     # shared materialisation: bitwise
                np.testing.assert_array_equal(
                    np.asarray(fused.drawn_p[s, q]), np.asarray(dp))
                np.testing.assert_array_equal(
                    float(fused.p_max[s, q, 0]), float(jnp.max(p0)))
            else:                    # separate programs: ulp-level drift
                np.testing.assert_allclose(
                    np.asarray(fused.drawn_p[s, q]), np.asarray(dp),
                    rtol=1e-5, atol=1e-8)
                np.testing.assert_allclose(
                    float(fused.p_max[s, q, 0]), float(jnp.max(p0)),
                    rtol=1e-5)


def test_fused_akr_stops_like_progressive(backend):
    """AKR over the fused outputs == akr_progressive over materialised
    probabilities, lane for lane (the stop rule consumes in-launch draw
    state — no re-scoring)."""
    case = dict(S=3, Q=2, N=512, d=32, T=16, K=1, valid_kind="mask",
                seed=7)
    query, index, valid, targets = _case(**case)
    fused = kops.fused_retrieve_stack(query, index, tau=0.1, valid=valid,
                                      targets=targets, n_topk=1)
    _, probs = kops.similarity_stack(query, index, tau=0.1, valid=valid)
    got = jax.vmap(jax.vmap(lambda d, p, pm: rt.akr_from_draws(
        d, p, pm, theta=0.9, beta=1.0, n_max=16)))(
            fused.draws, fused.drawn_p, fused.p_max[..., 0])
    for s in range(case["S"]):
        for q in range(case["Q"]):
            draws = categorical_from_targets(probs[s, q], targets[s, q])
            want = rt.akr_from_draws(
                draws, probs[s, q][draws].astype(jnp.float32),
                jnp.max(probs[s, q]), theta=0.9, beta=1.0, n_max=16)
            np.testing.assert_array_equal(np.asarray(got.draws[s, q]),
                                          np.asarray(want.draws))
            assert int(got.n_drawn[s, q]) == int(want.n_drawn)


def test_fused_topk_ties_go_to_the_lowest_lane(backend):
    """Exact score ties (duplicate rows, some in different scan blocks)
    and more top-k slots than valid rows (masked lanes tie at NEG_INF):
    the fused top-k is ``lax.top_k`` over the masked scores — value
    descending, ties to the lowest lane — on both backends."""
    rng = np.random.default_rng(3)
    d, n = 16, 1300
    rows = rng.standard_normal((n, d)).astype(np.float32)
    rows[1100] = rows[7] = rows[600] = rows[3]      # 4-way tie, 2 blocks
    index = jnp.asarray(np.stack([rows, rows]))
    query = jnp.asarray(np.stack([rows[3], rows[3]])[:, None])
    sizes = jnp.asarray([n, 2], jnp.int32)          # lane 1: 2 valid rows
    fused = kops.fused_retrieve_stack(
        query, index, tau=0.1, valid=sizes,
        targets=jnp.zeros((2, 1, 1), jnp.float32), n_topk=6)
    sims, _ = kops.similarity_stack(query, index, tau=0.1, valid=sizes)
    vmask = ref.as_valid_mask(sizes, n)
    masked = jnp.where(vmask[:, None, :], sims, ref.NEG_INF)
    want_v, want_i = jax.lax.top_k(masked, 6)
    np.testing.assert_array_equal(np.asarray(fused.topk_i),
                                  np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(fused.topk_v),
                                  np.asarray(want_v))
    assert list(np.asarray(fused.topk_i[0, 0, :4])) == [3, 7, 600, 1100]


def test_chunk_cdf_is_blocking_invariant():
    """The draw contract: folding the CDF one scan block at a time
    (the fused kernel, carry in between) gives the same bits as one
    fold over the whole vector (the materialised path)."""
    p = jax.random.uniform(jax.random.key(21), (3, 8 * DRAW_BLK)) / 2000
    whole = chunk_cdf(p, jnp.zeros((3, 1), jnp.float32))
    carry, parts = jnp.zeros((3, 1), jnp.float32), []
    for b in range(0, p.shape[1], 4 * DRAW_BLK):
        part = chunk_cdf(p[:, b:b + 4 * DRAW_BLK], carry)
        carry = part[:, -1:]
        parts.append(part)
    np.testing.assert_array_equal(np.asarray(whole),
                                  np.asarray(jnp.concatenate(parts, -1)))
    np.testing.assert_array_equal(np.asarray(whole[1]),
                                  np.asarray(blockwise_cdf(p[1])))


def test_chunk_cdf_replaces_the_cumsum_fold():
    """Why the definition changed: the previous chunk fold was
    ``jnp.cumsum`` within each chunk plus a ``jnp.cumsum`` over chunk
    totals. Its bits follow XLA's lowering of cumsum (an associative
    scan on the CPU, ``reduce_window`` on the TPU), and Mosaic cannot
    lower it inside the fused kernel at all. The log-step scan is built
    from adds, rolls and selects only, so it is one set of bits on every
    platform and in the kernel. It is the same quantity: on seeded
    inputs it agrees with the old fold to fp32 rounding, and with an
    exact float64 prefix sum to the same order."""
    p = jax.random.uniform(jax.random.key(22), (4, 6 * DRAW_BLK)) / 1500
    new = np.asarray(chunk_cdf(p, jnp.zeros((4, 1), jnp.float32)))
    chunks = p.reshape(4, 6, DRAW_BLK)
    cc = jnp.cumsum(chunks, axis=-1)
    ext = jnp.concatenate([jnp.zeros((4, 1)), cc[..., -1][:, :-1]], -1)
    old = np.asarray((cc + jnp.cumsum(ext, -1)[..., None]).reshape(4, -1))
    exact = np.cumsum(np.asarray(p, np.float64), axis=-1)
    np.testing.assert_allclose(new, old, rtol=2e-6, atol=0)
    np.testing.assert_allclose(new, exact, rtol=2e-6, atol=0)
    assert np.all(np.diff(new, axis=-1) >= 0)


def test_no_dense_output_in_fused_contract():
    """The launch-boundary contract the bandwidth win rests on: lowering
    the fused retrieval yields outputs totalling O(S·Q·(T+K)) elements —
    nothing O(S·Q·cap) crosses the boundary."""
    S, Q, N, d, T, K = 2, 3, 2048, 32, 8, 4
    fn = lambda q, x, v, t: kops.fused_retrieve_stack(
        q, x, tau=0.1, valid=v, targets=t, n_topk=K)
    args = (jax.ShapeDtypeStruct((S, Q, d), jnp.float32),
            jax.ShapeDtypeStruct((S, N, d), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.int32),
            jax.ShapeDtypeStruct((S, Q, T), jnp.float32))
    out = jax.eval_shape(fn, *args)
    n_out = sum(int(np.prod(o.shape))
                for o in jax.tree_util.tree_leaves(out))
    assert n_out == S * Q * (2 * T + 2 * K + 3)     # draws+dp+topk²+stats
    assert n_out < S * Q * N / 16                    # nowhere near dense

    lowered = jax.jit(fn).lower(*args)
    ca = lowered.cost_analysis() or {}
    out_bytes = [v for k, v in ca.items()
                 if k.startswith("bytes accessed output")]
    if out_bytes:    # backend reports per-output byte traffic: pin it
        assert max(out_bytes) < S * Q * N * 4 / 16


def _ingest(worlds, cfg, chunk=96):
    mgr = SessionManager(cfg, PixelEmbedder(dim=64), embed_dim=64)
    for sid, w in enumerate(worlds):
        mgr.create_session(sid)
        for i in range(0, w.total_frames, chunk):
            mgr.ingest_tick({sid: w.frames[i:i + chunk]})
    mgr.flush()
    return mgr


@pytest.fixture(scope="module")
def worlds():
    return [VideoWorld(WorldConfig(n_scenes=3 + s, seed=160 + s))
            for s in range(2)]


def _specs(worlds, strategy, budget=6, seed0=240):
    qsids = [0, 1, 0]
    qes = [OracleEmbedder(worlds[s], dim=64).embed_queries(
        worlds[s].make_queries(1, seed=seed0 + j))[0]
        for j, s in enumerate(qsids)]
    return [QuerySpec(sid=s, embedding=qes[j], strategy=strategy,
                      budget=budget) for j, s in enumerate(qsids)]


def test_executor_routes_fused_vs_dense(worlds):
    """sampling/akr/topk groups cost fused launches (no dense score
    tensor); BOLT keeps the dense fallback; ``fused=False`` forces
    dense for everything."""
    mgr = _ingest(worlds, VenusConfig())
    specs = (_specs(worlds, "sampling") + _specs(worlds, "akr")
             + _specs(worlds, "topk"))
    plan = mgr.plan(specs)
    assert len(plan.groups) == 3
    kops.reset_scan_counts()
    mgr.execute(plan)
    c = kops.scan_counts()
    assert c["fused_draw_launches"] == 3
    assert c["dense_score_launches"] == 0
    assert c["similarity_stack"] == 3      # PR-3 invariant unchanged

    kops.reset_scan_counts()
    mgr.execute(mgr.plan(_specs(worlds, "bolt")))
    c = kops.scan_counts()
    assert (c["fused_draw_launches"], c["dense_score_launches"]) == (0, 1)

    kops.reset_scan_counts()
    mgr.execute(mgr.plan(_specs(worlds, "akr")), fused=False)
    c = kops.scan_counts()
    assert (c["fused_draw_launches"], c["dense_score_launches"]) == (0, 1)


@pytest.mark.parametrize("strategy", ["sampling", "akr", "topk"])
def test_fused_and_dense_paths_identical(worlds, strategy):
    """The escape hatch is an A/B switch, not a semantic fork: twin
    managers answering the same specs through the fused and the dense
    executor paths return identical draws and frame ids."""
    cfg = VenusConfig()
    mgr_f, mgr_d = _ingest(worlds, cfg), _ingest(worlds, cfg)
    specs = _specs(worlds, strategy)
    got_f = mgr_f.execute(mgr_f.plan(specs), fused=True)
    got_d = mgr_d.execute(mgr_d.plan(specs), fused=False)
    for a, b in zip(got_f, got_d):
        np.testing.assert_array_equal(a.draws, b.draws)
        np.testing.assert_array_equal(a.frame_ids, b.frame_ids)
        assert a.n_drawn == b.n_drawn


# ---------------------------------------------------------------------------
# int8 quantised index
# ---------------------------------------------------------------------------


def test_int8_arena_end_to_end(worlds):
    """cfg.index_dtype="int8": the arena stores int8 rows + f32 scales
    (written by the same tick scatter), queries run unchanged, and every
    scan streams 4× fewer index bytes than the fp32 twin."""
    mgr8 = _ingest(worlds, VenusConfig(index_dtype="int8"))
    mgr32 = _ingest(worlds, VenusConfig())
    assert mgr8.arena.emb.dtype == jnp.int8
    assert mgr8.arena.emb_scale.shape == mgr8.arena.emb.shape[:2]
    # scales cover exactly the occupied rows (zero rows keep scale 0
    # until written; written rows get scale > 0)
    for s in range(2):
        size = mgr8[s].memory.size
        assert np.all(np.asarray(mgr8.arena.emb_scale[s, :size]) > 0)

    specs = _specs(worlds, "akr")
    kops.reset_scan_counts()
    res8 = mgr8.query_specs(specs)
    b8 = kops.scan_counts()["scan_bytes"]
    kops.reset_scan_counts()
    res32 = mgr32.query_specs(specs)
    b32 = kops.scan_counts()["scan_bytes"]
    assert b32 == 4 * b8 and b8 > 0
    assert all(len(r.frame_ids) > 0 for r in res8)
    # int8 is lossy vs fp32 — but fused vs dense on the SAME int8 index
    # stays draw-for-draw identical (same buffer, same canonical CDF)
    mgr8b = _ingest(worlds, VenusConfig(index_dtype="int8"))
    res8b = mgr8b.execute(mgr8b.plan(specs), fused=False)
    for a, b in zip(res8, res8b):
        np.testing.assert_array_equal(a.draws, b.draws)
        np.testing.assert_array_equal(a.frame_ids, b.frame_ids)
    del res32


def test_int8_slot_recycle_resets_scales(worlds):
    mgr = _ingest(worlds, VenusConfig(index_dtype="int8"))
    assert np.any(np.asarray(mgr.arena.emb_scale[0]) > 0)
    mgr.close_session(0)
    mgr.create_session(5)
    slot = mgr[5].memory.slot
    assert slot == 0                       # recycled, not grown
    assert np.all(np.asarray(mgr.arena.emb_scale[0]) == 0)


def test_int8_topk_recall_drift_bounded():
    """Quantisation is allowed to perturb ranks, not retrieval: on
    clustered data (the regime the index actually stores — cluster
    centroids), int8 top-k overlaps fp32 top-k ≥ 0.9 on average."""
    rng = np.random.default_rng(11)
    C, per, d, k = 8, 32, 64, 16
    centers = rng.standard_normal((C, d)).astype(np.float32)
    rows = np.repeat(centers, per, 0) + 0.15 * rng.standard_normal(
        (C * per, d)).astype(np.float32)
    q8 = jnp.asarray(quantise_rows(rows)[0])
    q32 = jnp.asarray(rows)
    valid = jnp.ones((rows.shape[0],), bool)
    overlaps = []
    for ci in range(C):
        query = jnp.asarray(centers[ci] + 0.05 * rng.standard_normal(d),
                            jnp.float32)[None]
        top32 = np.asarray(rt.topk_retrieve(
            kops.similarity(query, q32, tau=0.1, valid=valid)[0][0],
            valid, k))
        top8 = np.asarray(rt.topk_retrieve(
            kops.similarity(query, q8, tau=0.1, valid=valid)[0][0],
            valid, k))
        overlaps.append(len(set(top32) & set(top8)) / k)
    assert np.mean(overlaps) >= 0.9, overlaps
