"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``reduce(path, window="bench.window")`` reads the trace with JAX's own
reader and returns, inside the host span named ``window`` (the benchmark's
measured window, on the same clock as the device):

    window_s     the span's length
    busy_s       the union of the intervals in which an operation ran on
                 the device, averaged over the devices traced
    ops          {operation name: summed device seconds}, each operation
                 named by its HLO instruction (the event name up to " = ")
    kernels      {label: seconds}: for each set of instruction names in
                 ``kernels``, the union of its operations' intervals
    gaps         the idle intervals between device operations, each
                 attributed to the innermost benchmark span (``bench.*``)
                 that was open on the host at the gap's middle:
                 {span name: summed idle seconds}
    n_devices    devices traced

Device operations are the events of the ``XLA Ops`` line of each
``/device:...`` plane. Their names are HLO instructions of the compiled
program; ``kernel_instructions`` finds, in that program's text, the
instructions that run one Pallas kernel.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"


def find(out_dir: str) -> str:
    """The newest ``.xplane.pb`` under a trace directory."""
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str) -> dict:
    """``{"device": {plane: [(name, start_ns, end_ns)]}, "host": [(name,
    start_ns, end_ns)]}`` of a trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns, ev.end_ns)
                               for ev in line.events)
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"device": device, "host": host}


def _innermost(spans):
    """``t -> name`` of the shortest span open at ``t``."""
    marks = sorted([(s, 1, i) for i, (_n, s, _e) in enumerate(spans)]
                   + [(e, 0, i) for i, (_n, _s, e) in enumerate(spans)])
    cuts, names, active = [], [], set()
    for t, opening, i in marks:
        if opening:
            active.add(i)
        else:
            active.discard(i)
        best = min(active, key=lambda j: spans[j][2] - spans[j][1],
                   default=None)
        cuts.append(t)
        names.append(None if best is None else spans[best][0])

    def at(t):
        i = bisect.bisect_right(cuts, t) - 1
        name = names[i] if i >= 0 else None
        return name or "(no benchmark span)"
    return at


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event's HLO instruction: ``%fusion.3 = f32[..] ...``
    gives ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def kernel_instructions(hlo_text: str, marker: str) -> set:
    """The instructions of a compiled program (``Compiled.as_text()``) that
    run a Mosaic kernel whose op name holds ``marker``: the custom calls
    themselves, and every fusion that calls, directly or through other
    fusions, a computation holding one."""
    comp, holds, calls = None, {}, {}
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        if ('custom_call_target="tpu_custom_call"' in line
                and marker in line):
            holds.setdefault(comp, set()).add(name)
        called = _CALLS.search(line)
        if called:
            calls[name] = (comp, called.group(1))
    found = set().union(*holds.values()) if holds else set()
    kernel_comps = set(holds)
    grew = True
    while grew:
        grew = False
        for name, (comp, callee) in calls.items():
            if callee in kernel_comps and name not in found:
                found.add(name)
                if comp not in kernel_comps:
                    kernel_comps.add(comp)
                grew = True
    return found


_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def op_labels(hlo_text: str) -> dict:
    """``{instruction: the JAX op name in its metadata}`` of a compiled
    program, to say what an ``XLA Ops`` event ran."""
    out = {}
    for line in hlo_text.splitlines():
        m, label = _INSTR.match(line), _OP_NAME.search(line)
        if m and label:
            out[m.group(1)] = label.group(1)
    return out


def reduce(path: str, window: str = "bench.window",
           kernels: dict | None = None) -> dict:
    tr = load(path)
    spans = [h for h in tr["host"] if h[0] == window]
    if not spans:
        raise ValueError(f"no host span {window!r} in {path}")
    _, w0, w1 = max(spans, key=lambda h: h[2] - h[1])
    innermost = _innermost(tr["host"])
    kernels = kernels or {}
    ops, busy, gaps = {}, 0.0, {}
    kern = {label: 0.0 for label in kernels}
    for plane, events in tr["device"].items():
        cut = [(op_name(n), max(s, w0), min(e, w1)) for n, s, e in events
               if e > w0 and s < w1]
        for n, s, e in cut:
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
        for label, names in kernels.items():
            kern[label] += sum(e - s for s, e in _union(
                [(s, e) for n, s, e in cut if n in names])) * 1e-9
        merged = _union([(s, e) for _n, s, e in cut])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                name = innermost((s + e) / 2)
                gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    n = max(len(tr["device"]), 1)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy / n,
            "ops": {k: v / n for k, v in ops.items()},
            "kernels": {k: v / n for k, v in kern.items()},
            "gaps": {k: v / n for k, v in gaps.items()},
            "n_devices": len(tr["device"])}


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of ``{name: seconds}`` as ``[[name, s]]``."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
