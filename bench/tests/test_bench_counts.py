"""The benchmark's operation counts agree with the program's analytic
model (``core/complexity.py``, the paper's Table I/II conventions) at the
paper's widths and at a small size, for every lane kind a cell serves."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import counts  # noqa: E402

from repro.core.complexity import ComplexityConfig, stage_macs  # noqa: E402

PAPER = dict(f_mem=100, f_time=100, f_emb=100, m_r=10)
SMALL = dict(f_mem=16, f_time=8, f_emb=12, m_r=6)


@pytest.mark.parametrize("w", [PAPER, SMALL], ids=["paper", "small"])
@pytest.mark.parametrize("kind,k", [("student", 4), ("student", 2),
                                    ("teacher", None)])
def test_embedding_macs_match_the_analytic_model(w, kind, k):
    cc = ComplexityConfig(
        f_mem=w["f_mem"], f_time=w["f_time"], f_emb=w["f_emb"],
        m_r=w["m_r"], f_edge=172,
        attention="sat" if kind == "student" else "vanilla",
        encoder="lut" if kind == "student" else "cosine", prune_k=k)
    got = counts.embedding_macs(kind, k if k else w["m_r"], w, 172)
    assert got == stage_macs(cc)["total"]


def test_event_is_two_embeddings_of_two_flops_per_mac():
    assert counts.event_flops("student", 4, PAPER, 172) == \
        4 * counts.embedding_macs("student", 4, PAPER, 172)


def test_fused_kernel_counts_at_native_widths():
    # GRU 3*(372*100 + 100*100), value projection 4*272*100, weighted sum
    # 4*100, output 200*100 multiply-adds
    macs = 3 * (372 * 100 + 100 * 100) + 4 * 272 * 100 + 4 * 100 + 200 * 100
    assert counts.fused_row_flops(4, PAPER, 172) == 2 * macs
    # reads: message 372, memory 100, 3 scalars, 4 x (100 + 172 + 2);
    # writes: memory 100, embedding 100
    assert counts.fused_row_bytes(4, PAPER, 172) == \
        4 * (372 + 100 + 3 + 4 * 274 + 200)
