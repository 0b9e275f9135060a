"""What the benchmark's two cells generate and count, pinned: digests of
the stream at the configurations' full shapes and of the weights at a
small width, and every operation count at the configurations' widths,
taken before the harness learned node features and one-population
streams. A change that moves any of them moves what the cells measure."""
import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import counts  # noqa: E402
import reference  # noqa: E402
import stream  # noqa: E402

SEED = 2 ** 31 + 11

STREAMS = {
    "tgn-wikipedia":
        "3e1f41b7c555eeefbc06f3cc628413663d65d4698616d6c048cf62a5b795358a",
    "tgn-reddit-mixed":
        "5ee6fdf2b1c42430526bb454938997fea178c01fb76d044bf9b3a47e792bf780",
}

WEIGHTS = {
    ("student",):
        "8094bebe5c75db3791668ba27e65f208532c518fb1b27194d807daf134d77706",
    ("teacher",):
        "2bf3b3db80c797f29f7691ea985297cfdd935f79ff2a7ec4fc4c9cc153d05fe6",
    ("student", "teacher"):
        "c4a96fb00bafe5196b00af10eb73cb4452fabbd10e49996c00f8d9e6eb4a4aa6",
}

# per lane: embedding_macs, event_flops, fused_row_flops, fused_row_bytes
COUNTS = {
    ("tgn-wikipedia", "np4-fused"): [270900, 1083600, 541600, 7084],
    ("tgn-reddit-mixed", "np4-fused"): [270900, 1083600, 541600, 7084],
    ("tgn-reddit-mixed", "np4-reservoir-fused"):
        [270900, 1083600, 541600, 7084],
    ("tgn-reddit-mixed", "np2-staged"): [216300, 865200, 432400, 4892],
    ("tgn-reddit-mixed", "teacher-ref"): [958800, 3835200, 869200, 13660],
}


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(STREAMS))
def test_stream_at_full_shape_is_pinned(name):
    cfg = _config(name)
    g = stream.generate(stream.GraphShape(**cfg["graph"]), SEED)
    assert getattr(g, "node_feats", None) is None
    assert _digest([g.src, g.dst, g.ts, g.edge_feats]) == STREAMS[name]


@pytest.mark.parametrize("kinds", sorted(WEIGHTS))
def test_weights_are_pinned(kinds):
    w = dict(f_mem=16, f_time=16, f_emb=16, m_r=10, n_heads=2,
             lut_entries=128)
    bounds = reference.lut_boundaries(np.arange(1.0, 1001.0), 128)
    p = reference.make_weights(SEED, list(kinds), w, 172, bounds)
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == WEIGHTS[kinds]


@pytest.mark.parametrize("name,lane", sorted(COUNTS))
def test_counts_are_pinned(name, lane):
    cfg = _config(name)
    w = cfg["widths"]
    spec = next(x for x in cfg["lanes"] if x["name"] == lane)
    k, kind = spec.get("k", w["m_r"]), spec["params"]
    f_edge = cfg["graph"]["f_edge"]
    assert [counts.embedding_macs(kind, k, w, f_edge),
            counts.event_flops(kind, k, w, f_edge),
            counts.fused_row_flops(k, w, f_edge),
            counts.fused_row_bytes(k, w, f_edge)] == COUNTS[(name, lane)]
