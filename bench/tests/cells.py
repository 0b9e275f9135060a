"""Small sizes of the benchmark's cells for the CPU tests, and the faults
the check must catch, planted in the timed path."""
import copy
import os
import sys
import time

import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import harness  # noqa: E402

SEED = 2 ** 31 + 11


def tiny(cfg: dict) -> dict:
    """``cfg`` at a size the CPU interprets in seconds: 100 vertices (60
    users and 40 items, or one population of 100), 3,000 edges, widths of
    16, batches of 16, at most two tenants a lane."""
    cfg = copy.deepcopy(cfg)
    if cfg["graph"].get("bipartite", True):
        cfg["graph"].update(n_users=60, n_items=40, n_edges=3000)
    else:
        cfg["graph"].update(n_users=100, n_items=0, n_edges=3000)
    cfg["widths"].update(f_mem=16, f_time=16, f_emb=16)
    cfg["batch"] = 16
    for lane in cfg["lanes"]:
        lane["count"] = min(lane["count"], 2)
    return cfg


def gdelt_shaped() -> dict:
    """A GDELT-shaped configuration (arXiv:2203.05095, Table II: 200-d
    static node features, no edge features; events between the actors of
    one population), built from the Wikipedia configuration's widths: a
    staged student lane and a teacher lane on the XLA tier, with 16-d node
    features."""
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "tgn-wikipedia.json"))
    cfg["name"] = "gdelt-shaped"
    cfg["graph"].update(n_users=16682, n_items=0, f_edge=0, f_feat=16,
                        bipartite=False)
    cfg["lanes"] = [
        {"name": "np4-staged", "variant": "sat+lut+np4", "tier": "staged",
         "params": "student", "k": 4, "sampler": "recent", "count": 2},
        {"name": "teacher-ref", "variant": "vanilla+cosine", "tier": "ref",
         "params": "teacher", "k": 10, "sampler": "recent", "count": 2}]
    return cfg


def run(cell: str, seconds: float = 0.5, fault=None, control=False,
        traffic=None, cfg=None):
    """One run of ``cell`` at the small size; ``traffic`` names a mix in
    ``bench/traffic/`` to serve in place of the cell's own, and ``cfg`` a
    configuration in place of the cell's own."""
    spec = harness.load_spec()
    _, own, _ = harness.resolve(spec, cell)
    mix = (None if traffic is None
           else harness.load_json(harness.traffic_file(traffic)))
    return harness.run_cell(spec, cell, SEED, seconds, False,
                            time.monotonic(), lambda _m: None,
                            cfg=tiny(own if cfg is None else cfg),
                            fault=fault, control=control, traffic=mix)


def frozen_state(mgr):
    """Every round returns the state it was given."""
    step = mgr.step

    def f(batches):
        before = [c.state for c in mgr._cohorts.values()]
        outs = step(batches)
        for c, s in zip(mgr._cohorts.values(), before):
            c.state = s
        return outs
    mgr.step = f


def half_batch(mgr):
    """Each tenant's batch loses its second half (marked not valid)."""
    step = mgr.step

    def f(batches):
        cut = {}
        for tid, b in batches.items():
            b = [x.copy() for x in tuple(b)[:5]]
            b[4][len(b[4]) // 2:] = False
            cut[tid] = tuple(b)
        return step(cut)
    mgr.step = f


def altered_answer(mgr):
    """One embedding value of every tenant's answer is off by 1."""
    step = mgr.step

    def f(batches):
        outs = step(batches)
        return {t: o._replace(emb_src=o.emb_src.at[0, 0].add(1.0))
                for t, o in outs.items()}
    mgr.step = f


def zeroed_node_feats(mgr):
    """The program serves with every node feature zero."""
    mgr.node_feats = jnp.zeros_like(mgr.node_feats)


def zeroed_w_s(mgr):
    """Every lane's node-feature projection ``W_s`` (Eq. 11) is zero."""
    for c in mgr._cohorts.values():
        feat = c.params["attn"]["feat"]
        c.params = dict(c.params, attn=dict(
            c.params["attn"], feat=dict(feat, w_s=jnp.zeros_like(
                feat["w_s"]))))


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
# faults of a configuration with static node features
NODE_FEAT_FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
                    "zeroed_node_feats": zeroed_node_feats}
