"""Small sizes of the benchmark's cells for the CPU tests, and the faults
the check must catch, planted in the timed path."""
import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import harness  # noqa: E402

SEED = 2 ** 31 + 11


def tiny(cfg: dict) -> dict:
    """``cfg`` at a size the CPU interprets in seconds: 100 vertices,
    3,000 edges, widths of 16, batches of 16, at most two tenants a lane."""
    cfg = copy.deepcopy(cfg)
    cfg["graph"].update(n_users=60, n_items=40, n_edges=3000)
    cfg["widths"].update(f_mem=16, f_time=16, f_emb=16)
    cfg["batch"] = 16
    for lane in cfg["lanes"]:
        lane["count"] = min(lane["count"], 2)
    return cfg


def run(cell: str, seconds: float = 0.5, fault=None, control=False,
        traffic=None):
    """One run of ``cell`` at the small size; ``traffic`` names a mix in
    ``bench/traffic/`` to serve in place of the cell's own."""
    spec = harness.load_spec()
    _, cfg, _ = harness.resolve(spec, cell)
    mix = (None if traffic is None
           else harness.load_json(harness.traffic_file(traffic)))
    return harness.run_cell(spec, cell, SEED, seconds, False,
                            time.monotonic(), lambda _m: None,
                            cfg=tiny(cfg), fault=fault, control=control,
                            traffic=mix)


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


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
