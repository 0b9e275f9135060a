#!/usr/bin/env python3
"""The open-loop load generator: a child process that never imports JAX.

    python bench/loadgen.py --graph '{"n_users": ...}' \
        --traffic '{"rate_eps": ...}' --seed S --seconds N --tenants K \
        --skip W

It rebuilds the run's schedule from the same graph shape, traffic mix and
seed as the server
(each tenant's replay advanced past the ``--skip`` warm-up events), prints
``ready``, reads the server's port and the window's opening time
(``time.monotonic()``, a clock this process shares with the server) from
standard input, connects, and sends every
event over one JSON-lines connection when it is due, whatever the server
does. A reader thread takes the responses in order. At the end it prints
one JSON line: the indices of refused events and how late the sends ran.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import stream  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    for name in ("--seed", "--tenants", "--skip"):
        ap.add_argument(name, type=int, required=True)
    ap.add_argument("--graph", required=True,
                    help="the graph shape, as a JSON object")
    ap.add_argument("--traffic", required=True,
                    help="the traffic mix, as a JSON object")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    traffic = json.loads(args.traffic)
    graph = stream.generate(stream.GraphShape(**json.loads(args.graph)), args.seed)
    replays = [stream.TenantReplay(graph, s)
               for s in stream.tenant_starts(graph, args.tenants)]
    for rp in replays:
        rp.taken = args.skip
    sched = stream.schedule(traffic, replays, args.seconds, args.seed)
    lines = [json.dumps({"op": "ingest", "tid": f"t{t}", "src": int(s),
                         "dst": int(d), "eid": int(e), "ts": float(ts)})
             .encode() + b"\n"
             for t, s, d, e, ts in zip(sched.tenant, sched.src, sched.dst,
                                       sched.eid, sched.ts)]
    refused = []
    n = len(lines)

    def reader():
        for j in range(n):
            resp = json.loads(rfile.readline())
            if not resp.get("ok"):
                refused.append(j)

    print("ready", flush=True)
    port, t_open = sys.stdin.readline().split()
    t_open = float(t_open)
    sock = socket.create_connection(("127.0.0.1", int(port)))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    rfile = sock.makefile("rb")
    th = threading.Thread(target=reader, daemon=True)
    th.start()
    late = np.zeros(n)
    due = t_open + sched.due
    j = 0
    while j < n:
        now = time.monotonic()
        if now < due[j]:
            time.sleep(min(due[j] - now, 0.001))
            continue
        hi = int(np.searchsorted(due, now, side="right"))
        sock.sendall(b"".join(lines[j:hi]))
        late[j:hi] = time.monotonic() - due[j:hi]
        j = hi
    th.join()
    sock.close()
    print(json.dumps({"sent": n, "rejected": refused,
                      "lateness_p99_s": float(np.percentile(late, 99))
                      if n else 0.0,
                      "lateness_max_s": float(late.max()) if n else 0.0}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
