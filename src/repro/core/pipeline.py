"""The Venus system: online ingestion + querying (paper Fig. 6).

Ingestion (steps ①–④): stream chunks → scene segmentation → incremental
clustering per partition → centroid index frames → aux prompts → MEM
embedding → hierarchical memory insert. Querying (steps ⑤–⑦): embed the
query, similarity over the index (Eq. 4), temperature-softmax sampling or
AKR (Eq. 5–7), expand draws into raw frames from the cluster reservoirs,
hand the frame set to the (cloud) VLM.

The stage logic lives in ``repro.core.session`` as composable per-stream
stages driven by a ``SessionManager`` (multi-stream, batch-first).
``VenusSystem`` is the single-stream façade over one managed session —
the public API the examples/benchmarks were written against — and also
exposes the batched ``query_batch``.

The embedder is pluggable:
* ``MEMEmbedder`` — the real dual-tower MEM (frontend-stub patchifier).
* ``OracleEmbedder`` (repro.data.video) — a perfect MEM for isolating
  retrieval-algorithm quality in benchmarks.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aux_models import AuxModel
from repro.core.queryplan import QueryPlan, QuerySpec
from repro.core.session import (QueryResult, SessionManager, SessionState,
                                VenusConfig)
from repro.data.text import tokenize_batch
from repro.obs import span
from repro.util import pow2_bucket

__all__ = ["patchify", "MEMEmbedder", "VenusConfig", "QueryResult",
           "QuerySpec", "QueryPlan", "VenusSystem", "SessionManager",
           "SessionState"]


# ---------------------------------------------------------------------------
# Embedders
# ---------------------------------------------------------------------------


def patchify(frames: np.ndarray, patch: int, d_vision: int,
             seed: int = 11) -> jnp.ndarray:
    """Frontend stub: frames (B,H,W,3) -> patch embeddings (B,P,d_vision)
    via fixed seeded random projection of raw patches."""
    b, h, w, c = frames.shape
    ph, pw = h // patch, w // patch
    x = frames[:, : ph * patch, : pw * patch].reshape(
        b, ph, patch, pw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, ph * pw, patch * patch * c)
    rng = np.random.default_rng(seed)
    proj = rng.normal(0, 1.0 / np.sqrt(x.shape[-1]),
                      (x.shape[-1], d_vision)).astype(np.float32)
    return jnp.asarray(x @ proj)


class MEMEmbedder:
    """Adapter: Venus pipeline ↔ the dual-tower MEM model."""

    def __init__(self, mem, params, *, patch: int = 8,
                 text_max_len: int = 32):
        self.mem = mem
        self.params = params
        self.patch = patch
        self.text_max_len = text_max_len
        self._img_fn = jax.jit(mem.encode_image)
        self._txt_fn = jax.jit(mem.encode_text)

    def embed_frames(self, frames: np.ndarray,
                     aux_texts: Optional[Sequence[str]] = None,
                     frame_ids=None) -> np.ndarray:
        frames = np.asarray(frames)
        n = frames.shape[0]
        # pad the batch to a power-of-two bucket: multi-stream ticks close
        # arbitrary numbers of clusters, and unbucketed shapes would jit-
        # specialise the vision tower per batch size
        bucket = pow2_bucket(n, lo=4)
        if bucket != n:
            frames = np.concatenate(
                [frames, np.zeros((bucket - n,) + frames.shape[1:],
                                  frames.dtype)])
        with span("ingest.embed.patchify"):
            patches = patchify(frames, self.patch,
                               self.mem.cfg.vision.d_model)
        with span("ingest.embed.tower"):
            img = self._img_fn(self.params, patches)[:n]
            if aux_texts and any(aux_texts):
                toks, mask = tokenize_batch(list(aux_texts),
                                            self.mem.cfg.text.vocab_size,
                                            self.text_max_len)
                txt = self._txt_fn(self.params, jnp.asarray(toks),
                                   jnp.asarray(mask))
                img = (img + 0.3 * txt) / np.linalg.norm(
                    np.asarray(img + 0.3 * txt), axis=-1, keepdims=True)
            return np.asarray(img, np.float32)

    def embed_queries(self, texts: Sequence[str]) -> np.ndarray:
        """Batch-encode Q query texts in one text-tower call."""
        toks, mask = tokenize_batch(list(texts),
                                    self.mem.cfg.text.vocab_size,
                                    self.text_max_len)
        return np.asarray(self._txt_fn(self.params, jnp.asarray(toks),
                                       jnp.asarray(mask)), np.float32)

    def embed_query(self, text: str) -> np.ndarray:
        return self.embed_queries([text])[0]


# ---------------------------------------------------------------------------
# Venus system — single-stream façade over one managed session
# ---------------------------------------------------------------------------


class VenusSystem:
    def __init__(self, cfg: VenusConfig, embedder, embed_dim: int,
                 aux_models: Sequence[AuxModel] = (),
                 annotation_fn=None):
        self.cfg = cfg
        self.embedder = embedder
        self.manager = SessionManager(cfg, embedder, embed_dim,
                                      aux_models=aux_models,
                                      annotation_fn=annotation_fn)
        self.sid = self.manager.create_session()

    # ----------------------------------------------------- state delegation
    @property
    def _session(self) -> SessionState:
        return self.manager[self.sid]

    @property
    def memory(self):
        return self._session.memory

    @property
    def frames(self):
        return self._session.frames

    @property
    def stats(self) -> Dict[str, int]:
        return self._session.stats

    @property
    def segmenter(self):
        return self._session.segmenter

    # ------------------------------------------------------------ ingestion
    def ingest(self, chunk: np.ndarray) -> Dict[str, float]:
        """Consume a chunk of streaming frames (T,H,W,3). Returns stage
        timings for this chunk."""
        t = self.manager.ingest_tick({self.sid: chunk})
        return {"segment": t["segment"],
                "cluster_embed": t["cluster"] + t["embed_insert"]}

    def flush(self) -> None:
        self.manager.flush([self.sid])

    # -------------------------------------------------------------- querying
    def plan(self, specs: Sequence[QuerySpec]) -> QueryPlan:
        """Declarative path: group specs into execution groups. Specs
        are pinned to this system's single session."""
        return self.manager.plan(
            [replace(s, sid=self.sid) for s in specs])

    def execute(self, plan: QueryPlan) -> List[QueryResult]:
        return self.manager.execute(plan)

    def query_specs(self, specs: Sequence[QuerySpec]) -> List[QueryResult]:
        """``execute(plan(specs))`` — any registered retrieval strategy
        through the fused one-scan-per-group path."""
        return self.execute(self.plan(specs))

    def query(self, text: str, *, budget: Optional[int] = None,
              use_akr: bool = True, query_emb: Optional[np.ndarray] = None
              ) -> QueryResult:
        """budget set ⇒ fixed-N sampling (paper §IV-D1); otherwise AKR."""
        return self.manager.query(self.sid, text, budget=budget,
                                  use_akr=use_akr, query_emb=query_emb)

    def query_batch(self, texts: Optional[Sequence[str]] = None, *,
                    query_embs: Optional[np.ndarray] = None,
                    budget: Optional[int] = None, use_akr: bool = True
                    ) -> List[QueryResult]:
        """Q queries through one similarity scan + vmapped sampling."""
        return self.manager.query_batch(self.sid, texts,
                                        query_embs=query_embs,
                                        budget=budget, use_akr=use_akr)

    # baselines share the same memory/index ---------------------------------
    def query_topk(self, text: str, k: int,
                   query_emb: Optional[np.ndarray] = None) -> np.ndarray:
        return self.manager.query_topk(self.sid, text, k,
                                       query_emb=query_emb)
