"""Plain reference of retrieval over every retained row, and the numbers
that compare the program's answers with it.

For one query against one stream the reference scores every valid row
by cosine (float32 products at ``Precision.HIGHEST``, carried on in
float64), takes the temperature softmax and its running sum in float64,
and so knows the top-k ranking and, for a draw target t, the lane whose
CDF interval holds t. The draw targets come from the session's PRNG
chain as the program documents it: the session key starts at
``key(cfg.seed)`` and each stochastic query takes the second half of one
more split, in plan order; ``draw_targets`` turns it into
(randint(0, 2^20) + 0.5) / 2^20. Frame ids follow from the draws and the
known history: index frames for top-k, and for member strategies the
member ``(u * count) >> 20`` of each drawn row, u from
``default_rng(cfg.seed).integers(0, 2^20, budget)``.

``lowp`` computes the same answers one precision step below the
configuration — bfloat16 scores, softmax and CDF, and int4 rows for an
int8 index — for the control.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

U_BITS = 20


def quantise_int(rows: np.ndarray, levels: int) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """Symmetric per-row quantisation to integers in [-levels, levels]
    with scale max|row| / levels (scale 1 for an all-zero row)."""
    rows = np.asarray(rows, np.float32)
    scale = np.max(np.abs(rows), axis=-1) / np.float32(levels)
    scale = np.where(scale > 0, scale, np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(rows / scale[..., None]), -levels, levels)
    return q, scale


def stored_rows(rows: np.ndarray, index_dtype: str, lowp: bool = False
                ) -> np.ndarray:
    """The rows as the index keeps them, as float32 values the scan
    reads (for an integer index the integers themselves: the cosine
    does not see the per-row scale)."""
    if index_dtype == "int8":
        return quantise_int(rows, 7 if lowp else 127)[0].astype(np.float32)
    if lowp:
        return np.asarray(jnp.asarray(rows).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    return np.asarray(rows, np.float32)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _scores(rows, q, lowp: bool = False):
    if lowp:
        dt = jnp.bfloat16
        rows = rows.astype(dt)
        q = q.astype(dt)
        rn = rows * jax.lax.rsqrt(jnp.sum(rows * rows, -1, keepdims=True)
                                  + 1e-12).astype(dt)
        qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                               + 1e-12).astype(dt)
        return jnp.matmul(qn, rn.T, preferred_element_type=dt)
    rn = rows * jax.lax.rsqrt(jnp.sum(rows * rows, -1, keepdims=True))
    qn = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True))
    return jnp.matmul(qn, rn.T, precision=jax.lax.Precision.HIGHEST)


def scores(rows_dev, queries: np.ndarray, lowp: bool = False) -> np.ndarray:
    """(Q, N) cosine scores of queries against one stream's rows."""
    return np.asarray(_scores(rows_dev, jnp.asarray(queries, jnp.float32),
                              lowp).astype(jnp.float32), np.float64)


def chain_subkeys(seed: int, count: int) -> List:
    """The first ``count`` subkeys a session's PRNG chain hands out."""
    key, out = jax.random.key(seed), []
    for _ in range(count):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


def draw_targets(subkey, n: int) -> np.ndarray:
    u = jax.random.randint(subkey, (n,), 0, 1 << U_BITS)
    return np.asarray((u.astype(jnp.float32) + 0.5)
                      * jnp.float32(1.0 / (1 << U_BITS)), np.float64)


def softmax_cdf(s: np.ndarray, tau: float, lowp: bool = False
                ) -> Tuple[np.ndarray, np.ndarray]:
    if lowp:
        z = jnp.asarray(s / tau, jnp.bfloat16)
        p = jax.nn.softmax(z)
        cdf = jax.lax.associative_scan(jnp.add, p)
        return (np.asarray(p.astype(jnp.float32), np.float64),
                np.asarray(cdf.astype(jnp.float32), np.float64))
    z = s / tau
    p = np.exp(z - z.max())
    p /= p.sum()
    return p, np.cumsum(p)


def draw_lanes(cdf: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """First lane whose CDF exceeds each target, clipped to the last."""
    return np.minimum(np.searchsorted(cdf, targets, side="right"),
                      len(cdf) - 1)


def draw_gap(cdf: np.ndarray, lanes: np.ndarray, targets: np.ndarray
             ) -> float:
    """Largest distance of a target from the CDF interval of the lane
    drawn for it (0 when every drawn lane's interval holds its target)."""
    lanes = np.asarray(lanes, np.int64)
    if np.any((lanes < 0) | (lanes >= len(cdf))):
        return 1.0
    hi = cdf[lanes]
    lo = np.where(lanes > 0, cdf[np.maximum(lanes - 1, 0)], 0.0)
    return float(np.max(np.maximum(0.0, np.maximum(lo - targets,
                                                   targets - hi))))


def topk_gap(s: np.ndarray, lanes: np.ndarray) -> float:
    """Largest amount by which the reference score of the lane served at
    rank r lies below the reference's r-th best score."""
    lanes = np.asarray(lanes, np.int64)
    if np.any((lanes < 0) | (lanes >= len(s))):
        return 1.0
    best = np.sort(s)[::-1][:len(lanes)]
    return float(np.max(best - s[lanes]))


def akr_stop(p: np.ndarray, lanes: np.ndarray, theta: float, beta: float,
             n_max: int) -> int:
    """Eq. 6/7: stop at the first draw where the distinct drawn mass
    reaches theta*beta, and not before beta*ceil(theta / max p) draws."""
    n_min = int(min(max(beta * np.ceil(theta / max(p.max(), 1e-9)), 1),
                    n_max))
    seen, mass = set(), 0.0
    for i, lane in enumerate(lanes[:n_max]):
        if int(lane) not in seen:
            seen.add(int(lane))
            mass += p[int(lane)]
        if mass / beta >= theta and i + 1 >= n_min:
            return i + 1
    return n_max


def expand_members(draws: np.ndarray, first: np.ndarray, count: np.ndarray,
                   u: np.ndarray) -> np.ndarray:
    """Frame ids of member strategies: for each drawn row, member
    (u * count) >> 20 of its contiguous member run; deduplicated."""
    d = np.asarray(draws, np.int64)
    pick = (u[:len(d)].astype(np.int64) * count[d]) >> U_BITS
    return np.unique(first[d] + pick)


def expand_u(cfg_seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(cfg_seed).integers(
        0, 1 << U_BITS, size=n, dtype=np.int64)


def answer(s: np.ndarray, kind: Mapping, targets, tau: float,
           venus: Mapping, lowp: bool = False) -> Dict[str, np.ndarray]:
    """The answer the reference itself gives (for the control)."""
    if kind["strategy"] == "topk":
        order = np.argsort(-s.astype(np.float32 if lowp else np.float64),
                           kind="stable")
        return {"draws": order[:kind["budget"]]}
    p, cdf = softmax_cdf(s, tau, lowp)
    lanes = draw_lanes(cdf, targets)
    if kind["strategy"] == "akr":
        n = akr_stop(p, lanes, venus["theta"], venus["beta"],
                     kind["budget"])
        return {"draws": lanes[:n], "n_drawn": n}
    return {"draws": lanes}
