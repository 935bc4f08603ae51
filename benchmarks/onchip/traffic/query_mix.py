"""Open-loop query arrivals for a camera fleet.

Independent users ask about one camera each. Arrivals come at
``rate_qps`` with exponential gaps; the camera is drawn Zipf(``zipf_s``)
over the streams, the strategy from ``mix`` (each entry a strategy, its
budget and its share), the text from ``texts``.

So that a seed changes which question comes when and not how much
work a run holds, every seed gets the same arrivals and the same
multisets: n = round(rate * seconds) arrival times whose gaps are the n
exponential quantiles in one fixed order, the Zipf counts per
popularity rank rounded to n by largest remainder, the strategies in
their exact shares, and the texts in equal counts. The seed shuffles
the cameras, strategies and texts over the arrivals and decides which
stream holds which popularity rank.
"""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

from vbench.util import rng


def _apportion(weights: np.ndarray, n: int) -> np.ndarray:
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    cnt = np.floor(raw).astype(int)
    cnt[np.argsort(-(raw - cnt), kind="stable")[:n - cnt.sum()]] += 1
    return cnt


def schedule(params: Mapping, n_streams: int, seed: int, seconds: float
             ) -> Dict[str, np.ndarray]:
    rate = float(params["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    g = rng(seed, "queries")
    q = (np.arange(n) + 0.5) / n
    gaps = rng(0, "arrivals").permutation(-np.log1p(-q) / rate)
    t = np.cumsum(gaps) - gaps             # first arrival at 0
    t *= seconds / (t[-1] + gaps[-1])      # last gap ends at ``seconds``
    ranks = np.repeat(np.arange(n_streams), _apportion(
        (np.arange(n_streams) + 1.0) ** -params["zipf_s"], n))
    stream_of_rank = g.permutation(n_streams)
    mix = params["mix"]
    kinds = np.repeat(np.arange(len(mix)),
                      _apportion([m["share"] for m in mix], n))
    texts = np.repeat(np.arange(len(params["texts"])),
                      _apportion(np.ones(len(params["texts"])), n))
    return {"t": t,
            "sid": stream_of_rank[g.permutation(ranks)],
            "kind": g.permutation(kinds),
            "text": g.permutation(texts)}
