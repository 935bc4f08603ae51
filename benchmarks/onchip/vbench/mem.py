"""MEM as a configuration file states it: the program's model config,
its weights made from the seed, and a recorder around the embedder.

The weights are the benchmark's, made on the device in one jitted call
from the seed, in the layout the program's towers read. The plain
reference (``vbench.ref_mem``) reads the same layout, so both sides run
the same weights without either taking anything the other made.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Mapping, Tuple

import numpy as np


def tower_config(name: str, t: Mapping, mem: Mapping, *, learned: bool):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=t["num_layers"],
        d_model=t["d_model"], num_heads=t["num_heads"],
        num_kv_heads=t["num_heads"], head_dim=t["d_model"] // t["num_heads"],
        d_ff=t["d_ff"], vocab_size=t.get("vocab_size", 0),
        activation=mem["activation"], gated_mlp=False,
        pos_type="learned" if learned else "rope",
        max_seq_len=t["max_seq_len"], dtype=mem["dtype"],
        param_dtype=mem["param_dtype"])


def mem_config(mem: Mapping):
    from repro.configs.venus_mem import MEMConfig
    return MEMConfig(
        name=mem["name"], embed_dim=mem["embed_dim"],
        text=tower_config(mem["name"] + "-text", mem["text"], mem,
                          learned=False),
        vision=tower_config(mem["name"] + "-vision", mem["vision"], mem,
                            learned=True))


def _tower_params(key, t: Mapping, *, vocab: int, pos: int):
    import jax
    import jax.numpy as jnp
    L, d, ff = t["num_layers"], t["d_model"], t["d_ff"]
    ks = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    p = {"final_norm": {"w": jnp.ones((d,), jnp.float32)},
         "dense_blocks": {
             "ln1": {"w": jnp.ones((L, d), jnp.float32)},
             "ln2": {"w": jnp.ones((L, d), jnp.float32)},
             "attn": {"wq": dense(ks[0], (L, d, d), d),
                      "wk": dense(ks[1], (L, d, d), d),
                      "wv": dense(ks[2], (L, d, d), d),
                      "wo": dense(ks[3], (L, d, d), d)},
             "mlp": {"w_up": dense(ks[4], (L, d, ff), d),
                     "w_down": dense(ks[5], (L, ff, d), ff)}}}
    if vocab:
        p["embed"] = 0.02 * jax.random.normal(ks[6], (vocab, d), jnp.float32)
    if pos:
        p["pos_embed"] = 0.02 * jax.random.normal(ks[7], (pos, d),
                                                  jnp.float32)
    return p


@functools.lru_cache(maxsize=None)
def _params_fn(mem_json: str):
    import jax
    mem = json.loads(mem_json)

    def make(key):
        import jax.numpy as jnp
        ks = jax.random.split(key, 4)
        t, v, e = mem["text"], mem["vision"], mem["embed_dim"]
        return {
            "text": _tower_params(ks[0], t, vocab=t["vocab_size"], pos=0),
            "vision": _tower_params(ks[1], v, vocab=0,
                                    pos=v["max_seq_len"]),
            "text_proj": jax.random.normal(ks[2], (t["d_model"], e),
                                           jnp.float32) / np.sqrt(
                                               t["d_model"]),
            "vision_proj": jax.random.normal(ks[3], (v["d_model"], e),
                                             jnp.float32) / np.sqrt(
                                                 v["d_model"]),
            "logit_scale": jnp.asarray(2.0, jnp.float32),
            "logit_bias": jnp.asarray(-10.0, jnp.float32),
        }
    return jax.jit(make)


def make_params(mem: Mapping, seed: int):
    """MEM's weights from the seed, on the device, in one jitted call."""
    import jax
    return _params_fn(json.dumps(mem, sort_keys=True))(jax.random.key(seed))


class RecordingEmbedder:
    """The program's embedder, passed through, with every batch of query
    embeddings it returns kept beside the texts it was given — what the
    timed path produced, for the comparison after the window."""

    def __init__(self, inner):
        self.inner = inner
        self.queries: List[Tuple[List[str], np.ndarray]] = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def embed_queries(self, texts):
        out = self.inner.embed_queries(texts)
        self.queries.append((list(texts), out))
        return out

    def embed_frames(self, frames, aux_texts=None, frame_ids=None):
        return self.inner.embed_frames(frames, aux_texts,
                                       frame_ids=frame_ids)


def query_embeddings(rec: RecordingEmbedder) -> Dict[str, List[np.ndarray]]:
    out: Dict[str, List[np.ndarray]] = {}
    for texts, embs in rec.queries:
        for t, e in zip(texts, np.asarray(embs)):
            out.setdefault(t, []).append(e)
    return out
