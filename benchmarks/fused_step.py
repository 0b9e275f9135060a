"""Fused single-pass step kernel vs the staged kernel tier.

Three views of the same claim (the paper's §IV single-pass pipelining,
ported: after prune metadata, the step should touch HBM once):

  * launch count — kernel launches per compiled step: the staged tier pays
    one per unit (LUT encode + GRU + SAT aggregate), the fused tier ONE
    for the whole post-prune datapath (trace-time counter in kernels/ops);
  * materialized intermediate bytes — HLO-level accounting
    (launch/hlo_analysis.py) over the cross-lowered TPU module with the
    Pallas kernels as opaque custom-calls, counting only traffic through
    buffers the step itself materializes (the ``(B, k, Dkv)`` neighbor
    tensor, kv concats, inter-kernel operands); falls back to the
    jaxpr-level view when the toolchain cannot cross-lower;
  * host-backend wall clock — edges/s of the interpret-mode step on this
    host. NOTE: interpret mode executes the kernel as XLA ops, so this
    measures dispatch/fusion structure, not TPU DMA overlap; the byte
    accounting above is the hardware-relevant metric.

    PYTHONPATH=src python -m benchmarks.fused_step
"""
from __future__ import annotations

import time


def sweep(batch_sizes=(64, 256), rounds: int = 10, n_edges: int = 3000,
          f_mem: int = 100, variant: str = "sat+lut+np4"):
    """Rows of staged-vs-fused metrics, one per batch size."""
    import jax
    import jax.numpy as jnp

    from repro.core import pipeline as pl, tgn
    from repro.data import stream as stream_mod
    from repro.data import temporal_graph as tgd
    from repro.kernels import ops as kops
    from repro.launch import hlo_analysis as hlo

    g = tgd.wikipedia_like(n_edges=n_edges)
    dims = dict(n_nodes=g.cfg.n_nodes, n_edges=g.n_edges, f_edge=172,
                f_mem=f_mem, f_time=f_mem, f_emb=f_mem, m_r=10)
    cfg = pl.variant_config(variant, **dims)
    params = tgn.init_params(jax.random.key(0), cfg)
    # the layout a serving session keeps resident (ops.row_table)
    ef = kops.row_table(jnp.asarray(g.edge_feats))

    import numpy as np

    rows = []
    for B in batch_sizes:
        batches = [tuple(jnp.asarray(x) for x in
                         (b.src, b.dst, b.eid, b.ts, b.valid))
                   for b in stream_mod.fixed_count(
                       g, B, window=slice(0, min(B * (rounds + 3),
                                                 g.n_edges)))]
        per_tier = {}
        for tier in ("staged", "fused"):
            pipe = pl.build_pipeline(cfg, use_kernels=tier)
            aux = pipe.prepare(params)

            def fn(s, b, _pipe=pipe, _aux=aux):
                return _pipe.step(params, _aux, s, b, ef)

            # launches per compiled step (trace-time pallas-call counter)
            state0 = pipe.resident(pipe.init_state())
            kops.reset_launch_count()
            jax.jit(fn).lower(state0, batches[0])
            launches = kops.launch_count()

            # materialized intermediate HBM bytes (kernels opaque)
            with kops.force_interpret(False):
                traffic = hlo.step_traffic(fn, state0, batches[0])

            # compile + warm into steady state (ring buffers filling)
            step = jax.jit(fn)
            state = state0
            for b in batches[:3]:
                state = step(state, b).state
            jax.block_until_ready(state)
            per_tier[tier] = {"launches": launches,
                              "bytes": float(traffic["bytes"]),
                              "accounting": traffic["accounting"],
                              "step": step, "state": state, "walls": []}

        # host-backend wall clock (interpret mode, the only backend this
        # host has): the tiers' rounds are INTERLEAVED and summarized by
        # the median so background load skews both equally.
        for b in batches[3:rounds + 3]:
            for t in ("staged", "fused"):
                pt = per_tier[t]
                t0 = time.perf_counter()
                pt["state"] = pt["step"](pt["state"], b).state
                jax.block_until_ready(pt["state"])
                pt["walls"].append(time.perf_counter() - t0)
        for pt in per_tier.values():
            pt["eps"] = B / float(np.median(pt["walls"]))
            del pt["step"], pt["state"], pt["walls"]
        s, f = per_tier["staged"], per_tier["fused"]
        rows.append({
            "batch": B, "variant": variant, "f_mem": f_mem,
            "staged_launches": s["launches"], "fused_launches": f["launches"],
            "staged_bytes": round(s["bytes"]), "fused_bytes": round(f["bytes"]),
            "bytes_reduction": round(1.0 - f["bytes"] / s["bytes"], 3),
            "staged_eps": round(s["eps"]), "fused_eps": round(f["eps"]),
            "speedup": round(f["eps"] / s["eps"], 2) if s["eps"] else 0.0,
            "accounting": f["accounting"],
        })
    return rows


def main(full: bool = False):
    from benchmarks.common import save_json

    print("== fused single-pass step vs staged kernels ==")
    rows = sweep(batch_sizes=(64, 256) if not full else (64, 256, 512))
    for r in rows:
        print(f"  B={r['batch']:4d} launches {r['staged_launches']}->"
              f"{r['fused_launches']}  intermediates "
              f"{r['staged_bytes']/1e6:7.2f}->{r['fused_bytes']/1e6:7.2f} MB"
              f" (-{r['bytes_reduction']:.0%})  host "
              f"{r['staged_eps']:7d}->{r['fused_eps']:7d} E/s "
              f"({r['speedup']:.2f}x)")
    save_json("fused_step.json", {"sweep": rows})


if __name__ == "__main__":
    main()
