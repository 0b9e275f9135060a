"""Operations and bytes the algorithm requires, from shapes alone.

These count the work itself, not one implementation of it: native widths
(f_mem, f_edge, f_feat, f_time, f_emb; never a kernel's 128-lane padding), four
bytes per element, parameters resident on the chip, one multiply-add as
two operations. An event (one edge) makes two embeddings, one per
endpoint. The conventions follow the co-design paper's per-embedding
counts (arXiv:2203.05095, Tables I and II): a LUT time encoder folded
into the next layer costs no multiply-adds, and SAT scores come from the
neighbours' time deltas alone. Static node features (``f_feat``, 0 where
the data set has none) cost each embedding their projection ``W_s f``
(Eq. 11) and the read of the vertex's own feature row.
"""
from __future__ import annotations

WORD = 4  # bytes per float32 or int32 element


def embedding_macs(kind: str, k: int, w: dict, f_edge: int,
                   f_feat: int = 0) -> int:
    """Multiply-adds of one dynamic embedding, memory update included.
    ``kind`` is "student" (SAT attention, LUT encoder, ``k`` neighbours
    kept) or "teacher" (attention over all m_r neighbours, cosine
    encoder)."""
    m, t, d, mr = w["f_mem"], w["f_time"], w["f_emb"], w["m_r"]
    raw = 2 * m + f_edge
    out = f_feat * m + (m + d) * d
    if kind == "student":
        gru = 3 * raw * m + 3 * m * m
        gnn = mr * mr + k * (m + f_edge) * d + k * d + out
    else:
        gru = t + 3 * (raw + t) * m + 3 * m * m
        kv = m + f_edge + t
        gnn = (t * (1 + mr) + (m + t) * d + 2 * mr * kv * d + 2 * mr * d
               + out)
    return gru + gnn


def event_flops(kind: str, k: int, w: dict, f_edge: int,
                f_feat: int = 0) -> int:
    """Operations of one event: two embeddings."""
    return 2 * 2 * embedding_macs(kind, k, w, f_edge, f_feat)


def fused_row_flops(k: int, w: dict, f_edge: int, f_feat: int = 0) -> int:
    """Operations of one row of the fused step kernel: the memory update
    (GRU on the cached message, time rows folded) and the embedding from
    ``k`` kept neighbours (value projection, weighted sum, node-feature
    projection, output)."""
    m, d = w["f_mem"], w["f_emb"]
    gru = 3 * (2 * m + f_edge) * m + 3 * m * m
    eu = k * (m + f_edge) * d + k * d + f_feat * m + (m + d) * d
    return 2 * (gru + eu)


def fused_row_bytes(k: int, w: dict, f_edge: int, f_feat: int = 0) -> int:
    """Bytes one row of the fused step kernel must move: its vertex's
    cached message, memory, node-feature row, message time, last update
    and message flag; per kept neighbour its memory row, edge-feature row,
    time delta and score; and the updated memory and the embedding it
    writes."""
    m, d = w["f_mem"], w["f_emb"]
    reads = (2 * m + f_edge) + m + f_feat + 3 + k * (m + f_edge + 2)
    writes = m + d
    return WORD * (reads + writes)
