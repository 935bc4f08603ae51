"""Operations and bytes the work needs, from shapes alone.

Kernel rooflines and the whole-step shares divide these by measured
device or wall time. They count what the algorithm needs, not what an
implementation happens to move: a fused scan that streams every slot of
the arena twice is charged for one pass over the targeted streams only,
so a later change that skips untargeted slots or fuses the two passes
shows as a higher share.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple


def tower_flops(layers: int, d: int, d_ff: int, tokens: int) -> float:
    """One transformer tower over ``tokens`` positions: the four
    attention projections, the two MLP matmuls, and the score and value
    products of full (tokens x tokens) attention — 2 FLOPs per
    multiply-add."""
    proj = 2.0 * tokens * d * (4 * d)
    mlp = 2.0 * tokens * d * d_ff * 2
    attn = 2.0 * 2 * tokens * tokens * d
    return layers * (proj + mlp + attn)


def vision_flops_per_frame(mem: Mapping) -> float:
    """MEM's image tower per frame: (image/patch)^2 patch tokens, plus
    the projection into the shared space."""
    v = mem["vision"]
    tokens = (mem["image_size"] // mem["patch"]) ** 2
    return (tower_flops(v["num_layers"], v["d_model"], v["d_ff"], tokens)
            + 2.0 * v["d_model"] * mem["embed_dim"])


def text_flops(mem: Mapping, tokens: int) -> float:
    """MEM's text tower over one text of ``tokens`` real tokens (padding
    is not work a text needs), plus the projection."""
    t = mem["text"]
    return (tower_flops(t["num_layers"], t["d_model"], t["d_ff"], tokens)
            + 2.0 * t["d_model"] * mem["embed_dim"])


def scan_work(dim: int, itemsize: int, groups: Iterable[Tuple[int, int]],
              n_out: int) -> Tuple[float, float]:
    """(FLOPs, bytes) one fused retrieval scan needs.

    ``groups`` lists, for each stream the scan targets, (valid rows of
    that stream, queries against it). FLOPs are 2*dim per query per
    valid row; bytes are each targeted stream's valid rows read once,
    its queries (f32) and n_out f32/int32 outputs per query."""
    flops = 0.0
    nbytes = 0.0
    for rows, queries in groups:
        flops += 2.0 * dim * rows * queries
        nbytes += rows * dim * itemsize + queries * (dim + n_out) * 4
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  bytes_per_s: float) -> Tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def least_scan_seconds(cfg: Mapping, peaks: Mapping, query_ticks) -> float:
    """Least chip time of every fused scan the query ticks ran: per
    group, each targeted stream's valid rows once (the arena is full, so
    every row is valid), the group's queries, and per query its draw
    targets in and its draws, drawn probabilities, top-k and softmax
    statistics out."""
    dim = cfg["mem"]["embed_dim"]
    int8 = cfg["venus"]["index_dtype"] == "int8"
    rows = cfg["venus"]["memory_capacity"]
    peak = peaks["int8_ops"] if int8 else peaks["bf16_flops"]
    total = 0.0
    for tick in query_ticks:
        for strategy, budget, order in tick["groups"]:
            targets = 1 if strategy == "topk" else budget
            topk = budget if strategy == "topk" else 1
            flops, nbytes = scan_work(dim, 1 if int8 else 4,
                                      [(rows, n) for _, n in order],
                                      3 * targets + 2 * topk + 3)
            total += least_seconds(flops, nbytes, peak,
                                   peaks["hbm_bytes_per_s"])[0]
    return total
