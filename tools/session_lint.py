#!/usr/bin/env python
"""Staging/fusion-regression guard for the serving hot paths (make lint).

Two invariants, both enforced by walking ASTs (a regression here would be
silent — everything still computes the right numbers, just slower):

1. The coalesced round path in ``src/repro/serving/session.py`` must stay
   allocation-free on the host: batches are written in place into the
   pre-allocated ``_HostStager`` ring buffers and shipped with ONE
   ``device_put`` per round. A ``jnp.pad`` / ``jnp.stack`` /
   ``jnp.asarray`` / ``jnp.concatenate`` creeping back into that path
   reintroduces exactly the per-tenant-per-round device dispatches the
   coalesced design removed.

   The per-cohort baseline (``_percohort_round`` / ``_cohort_round`` /
   ``_as_device_tuple`` / ``_pad_dev`` / ``_idle_dev``) is exempt BY
   DESIGN: it is kept as the measured comparison point for
   ``benchmarks/multitenant.py`` and intentionally stages through device
   ops.

2. The fused single-pass step path must never re-materialize what the one
   launch exists to avoid: in ``stages.make_fused_step``'s ``datapath``
   and in ``kernels/ops.fused_step`` no ``jnp.concatenate``/``jnp.stack``
   (the kv concat) and no subscript gather of ``.memory`` / ``.mail`` /
   ``edge_feats`` / ``node_feats`` (the ``(B, k, Dkv)`` neighbor tensor
   and the tile's own feature rows — every row is DMA'd inside the
   kernel, everything XLA-side is ids/timestamps metadata); and
   ``kernels/fused_step.py`` itself must stay concat-free (the kernel
   computes split matmuls).

3. The round hot path must stay ASYNC: no unconditional
   ``block_until_ready`` (a device fence serializes the pipelined
   launches) and no stray ``time.perf_counter`` timing (each one is a
   host sync point temptation) outside the SAMPLED-trace gate. The
   observability layer (src/repro/obs) fences only on rounds the
   ``RoundTracer`` samples, inside an ``if trace ...:`` / ``if ...
   sampled ...:`` conditional — this rule pins that shape, so span
   accuracy can never quietly become an every-round drain.
   (``SessionManager.step`` keeps its by-design round-wall
   ``perf_counter`` pair — only its fences are guarded.)
   The profiler span helper ``obs.span`` is accepted in ``step``,
   ``_coalesced_round`` and ``_HostStager.stage``; the helper itself
   (``span``, ``_Recorded``) must hold no fence or ``perf_counter`` at
   all, and neither may the body of any ``with span("session.…")`` /
   ``span("frontend.…")`` site outside the sampled-trace gate, wherever
   it sits. Two spans hold one probe by design: ``session.stage_wait``
   IS the stager's reuse gate (its ``block_until_ready`` is allowed
   there and nowhere else in ``_HostStager.stage``), and
   ``session.step`` holds ``step``'s round-wall ``perf_counter`` pair.

4. Fault-injection hooks must stay NO-OP gated: every call to a
   ``FaultInjector`` hook (``on_round`` / ``before_launch`` /
   ``on_ingest`` / ``on_snapshot_write``) in the serving hot paths must
   sit inside an ``if`` whose test references the injector (``if faults
   is not None:``, ...). An ungated hook call puts a Python attribute
   lookup + dispatch on every production round/event even when no fault
   plan is armed — the injection layer's contract is strictly zero cost
   when disarmed (see docs/ROBUSTNESS.md).

5. Journal hooks on the ingest hot path must stay armed-gated the same
   way: every ``EventJournal`` call (append / flush-marker / dedup
   query) in ``ServingFrontend.submit``/``pump`` must sit inside an
   ``if`` whose test references the journal (``if self.journal is not
   None:``, ...). A fleet that never arms a journal pays one attribute
   test per event and NO disk IO (docs/ROBUSTNESS.md, "Recovery
   semantics").

6. The coalesced round hands each tenant its outputs from ONE compiled
   split per cohort (``session._split_out``): inside the loops of
   ``SessionManager._coalesced_round`` no call to ``_slice_out`` and no
   subscript of a round output's leaf (``out.emb_src[i]``, ...). Each
   is an eager device program per tenant per round; at a fleet's width
   those dispatches, not the device, set the round's rate. The
   per-cohort baseline and ``peek`` keep ``_slice_out`` by design.

Exits non-zero listing every violation; also fails if a guarded function
disappears (a rename must update this guard, not silently skip it).
"""
from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: jnp attributes that mean per-batch device staging is back (rule 1).
STAGING = {"pad", "stack", "asarray", "concatenate"}
#: jnp attributes that mean the fused datapath re-materializes (rule 2).
FUSING = {"concatenate", "stack"}
#: subscripted names/attributes that mean a neighbor-row gather is back.
GATHERS = {"memory", "mail", "edge_feats", "node_feats"}

#: file -> ((scope, function, banned jnp attrs, ban gathers?), ...)
#: ``scope`` is a class name, "*" for any nesting (module / closure), or
#: None for module level.
GUARDED = {
    os.path.join("src", "repro", "serving", "session.py"): (
        (None, "_as_host_tuple", STAGING, False),
        ("_HostStager", "stage", STAGING, False),
        ("SessionManager", "step", STAGING, False),
        ("SessionManager", "_coalesced_round", STAGING, False),
        ("SessionManager", "_ensure_layout", STAGING, False),
    ),
    os.path.join("src", "repro", "core", "stages.py"): (
        ("*", "datapath", FUSING, True),
    ),
    os.path.join("src", "repro", "kernels", "ops.py"): (
        (None, "fused_step", FUSING, True),
    ),
    os.path.join("src", "repro", "kernels", "fused_step.py"): (
        ("*", "_fused_kernel", FUSING, False),
        ("*", "fused_step_pallas", FUSING, False),
    ),
}

#: names whose call is a host sync point / timing probe (rule 3).
FENCES = {"block_until_ready", "perf_counter"}

#: file -> ((scope, function, banned fence names), ...). Same scope
#: conventions as GUARDED. ``SessionManager.sync()`` is exempt by design
#: (the explicit drain the callers opt into); ``_HostStager.stage``'s
#: reuse-gate wait is allowed only inside its ``session.stage_wait``
#: span (SPAN_ALLOWS).
FENCE_GUARDED = {
    os.path.join("src", "repro", "serving", "session.py"): (
        # step()'s round-wall perf_counter pair is the metrics contract;
        # only fences are banned there
        ("SessionManager", "step", {"block_until_ready"}),
        ("SessionManager", "_coalesced_round", FENCES),
        ("SessionManager", "_percohort_round", FENCES),
        ("_HostStager", "stage", FENCES),
    ),
    os.path.join("src", "repro", "core", "pipeline.py"): (
        ("CoalescedRound", "__call__", FENCES),
        ("*", "round_fn", FENCES),
    ),
    # the profiler span helper runs on every round: never a fence
    os.path.join("src", "repro", "obs", "trace.py"): (
        (None, "span", FENCES),
        ("_Recorded", "__enter__", FENCES),
        ("_Recorded", "__exit__", FENCES),
    ),
}

#: span-name prefixes whose ``with span(...)`` sites are checked for
#: fences wherever they sit (rule 3), and the files they sit in.
SPAN_PREFIXES = ("session.", "frontend.")
SPAN_FILES = (os.path.join("src", "repro", "serving", "session.py"),
              os.path.join("src", "repro", "serving", "frontend.py"))
#: the probes a span's body may hold by design (rule 3).
SPAN_ALLOWS = {"session.stage_wait": {"block_until_ready"},
               "session.step": {"perf_counter"}}

#: FaultInjector hook methods whose call must be fault-gated (rule 4).
FAULT_HOOKS = {"on_round", "before_launch", "on_ingest",
               "on_snapshot_write", "on_journal_append"}

#: file -> ((scope, function), ...): hot-path functions that are allowed
#: to call FAULT_HOOKS, but only under an ``if ... fault ...:`` gate.
FAULT_GUARDED = {
    os.path.join("src", "repro", "serving", "session.py"): (
        ("SessionManager", "step"),
    ),
    os.path.join("src", "repro", "serving", "frontend.py"): (
        ("ServingFrontend", "submit"),
        ("ServingFrontend", "pump"),
    ),
    os.path.join("src", "repro", "serving", "cluster.py"): (
        ("*", "work"),
    ),
}

#: EventJournal methods whose ingest-hot-path call must be journal-gated
#: (rule 5). ``append_event`` and ``note_flush`` are the disk writes;
#: ``is_duplicate``/``last_seq`` are the per-event dedup queries.
JOURNAL_HOOKS = {"append_event", "note_flush", "is_duplicate",
                 "last_seq"}

#: file -> ((scope, function), ...): hot-path functions allowed to call
#: JOURNAL_HOOKS, but only under an ``if ... journal ...:`` gate.
JOURNAL_GUARDED = {
    os.path.join("src", "repro", "serving", "frontend.py"): (
        ("ServingFrontend", "submit"),
        ("ServingFrontend", "pump"),
    ),
}

#: ``BatchOut`` leaves whose subscript in a loop cuts per-tenant outputs
#: one eager program at a time (rule 6).
OUT_LEAVES = {"emb_src", "emb_dst", "attn_logits", "nbr_valid", "nbr_dt"}

#: file -> ((scope, function), ...): round functions whose loops may not
#: cut per-tenant outputs out of the stacked round (rule 6).
OUTPUT_GUARDED = {
    os.path.join("src", "repro", "serving", "session.py"): (
        ("SessionManager", "_coalesced_round"),
    ),
}

#: the nodes that repeat their body (rule 6).
LOOPS = (ast.For, ast.While, ast.ListComp, ast.SetComp, ast.DictComp,
         ast.GeneratorExp)


def _functions(tree: ast.Module) -> dict:
    """(scope, name) -> FunctionDef; scope is the enclosing class for
    methods, None for module level, and every function is ALSO indexed
    under the wildcard scope "*" (closures inside factories)."""
    found = {}

    def visit(node, cls):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, ast.FunctionDef):
                found.setdefault(("*", sub.name), sub)
                found[(cls, sub.name)] = found.get((cls, sub.name), sub)
                visit(sub, cls)
            elif isinstance(sub, ast.ClassDef):
                for fn in sub.body:
                    if isinstance(fn, ast.FunctionDef):
                        found[(sub.name, fn.name)] = fn
                        found.setdefault(("*", fn.name), fn)
                        visit(fn, sub.name)
            else:
                visit(sub, cls)

    visit(tree, None)
    return found


def _violations(fn: ast.FunctionDef, banned: set, gathers: bool) -> list:
    out = []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "jnp" and node.attr in banned):
            out.append((node.lineno, f"jnp.{node.attr}"))
        if gathers and isinstance(node, ast.Subscript):
            v = node.value
            name = (v.attr if isinstance(v, ast.Attribute)
                    else v.id if isinstance(v, ast.Name) else None)
            if name in GATHERS:
                out.append((node.lineno, f"subscript gather of {name!r}"))
    return out


def _is_trace_gate(test: ast.expr) -> bool:
    """True when an ``if`` test references the sampled-trace gate — any
    name/attribute containing "trace" or "sampled" (``if trace is not
    None:``, ``if self.tracer.would_sample():``, ...)."""
    for n in ast.walk(test):
        ident = (n.id if isinstance(n, ast.Name)
                 else n.attr if isinstance(n, ast.Attribute) else "")
        if "trace" in ident or "sampled" in ident:
            return True
    return False


def _span_names(node: ast.With) -> list:
    """The names of the ``span("...")`` calls a ``with`` opens."""
    out = []
    for item in node.items:
        call = item.context_expr
        if (isinstance(call, ast.Call) and call.args
                and isinstance(call.args[0], ast.Constant)
                and isinstance(call.args[0].value, str)):
            f = call.func
            name = (f.attr if isinstance(f, ast.Attribute)
                    else f.id if isinstance(f, ast.Name) else None)
            if name == "span":
                out.append(call.args[0].value)
    return out


def _fence_violations(fn: ast.AST, banned: set) -> list:
    """Fence/timing calls reachable UNCONDITIONALLY (i.e. outside every
    sampled-trace-gated ``if`` body) inside ``fn``; inside a ``with
    span(name)`` block, the probes ``SPAN_ALLOWS[name]`` are allowed."""
    out = []

    def visit(node, gated, banned):
        if isinstance(node, ast.If) and _is_trace_gate(node.test):
            for b in node.body:
                visit(b, True, banned)
            for b in node.orelse:
                visit(b, gated, banned)
            return
        if isinstance(node, ast.With):
            inner = set(banned)
            for name in _span_names(node):
                inner -= SPAN_ALLOWS.get(name, set())
            for item in node.items:
                visit(item, gated, banned)
            for b in node.body:
                visit(b, gated, inner)
            return
        ident = (node.attr if isinstance(node, ast.Attribute)
                 else node.id if isinstance(node, ast.Name) else None)
        if not gated and ident in banned:
            out.append((node.lineno, ident))
        for sub in ast.iter_child_nodes(node):
            visit(sub, gated, banned)

    visit(fn, False, banned)
    return out


def _span_site_violations(tree: ast.AST) -> list:
    """``(lineno, span name, probe)`` for each fence/timing call outside
    the sampled-trace gate in the body of a ``with span(...)`` whose
    name starts with one of SPAN_PREFIXES."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        names = [n for n in _span_names(node) if n.startswith(SPAN_PREFIXES)]
        if names:
            out.extend((lineno, names[0], what)
                       for lineno, what in _fence_violations(node, FENCES))
    return out


def _is_fault_gate(test: ast.expr) -> bool:
    """True when an ``if`` test references the fault injector — any
    name/attribute containing "fault" (``if faults is not None:``,
    ``if self._faults:``, ...)."""
    for n in ast.walk(test):
        ident = (n.id if isinstance(n, ast.Name)
                 else n.attr if isinstance(n, ast.Attribute) else "")
        if "fault" in ident.lower():
            return True
    return False


def _fault_violations(fn: ast.FunctionDef) -> list:
    """FAULT_HOOKS calls reachable outside every fault-gated ``if``
    body inside ``fn``."""
    out = []

    def visit(node, gated):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, ast.If) and _is_fault_gate(sub.test):
                for b in sub.body:
                    visit(b, True)
                for b in sub.orelse:
                    visit(b, gated)
                continue
            if (not gated and isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in FAULT_HOOKS):
                out.append((sub.lineno, sub.func.attr))
            visit(sub, gated)

    visit(fn, False)
    return out


def _is_journal_gate(test: ast.expr) -> bool:
    """True when an ``if`` test references the journal — any name/
    attribute containing "journal" (``if self.journal is not None:``,
    ``if journal:``, ...)."""
    for n in ast.walk(test):
        ident = (n.id if isinstance(n, ast.Name)
                 else n.attr if isinstance(n, ast.Attribute) else "")
        if "journal" in ident.lower():
            return True
    return False


def _journal_violations(fn: ast.FunctionDef) -> list:
    """JOURNAL_HOOKS calls reachable outside every journal-gated ``if``
    body (and outside ``except`` handlers that re-gate on the journal)
    inside ``fn``."""
    out = []

    def visit(node, gated):
        for sub in ast.iter_child_nodes(node):
            if isinstance(sub, ast.If) and _is_journal_gate(sub.test):
                for b in sub.body:
                    visit(b, True)
                for b in sub.orelse:
                    visit(b, gated)
                continue
            if (not gated and isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in JOURNAL_HOOKS):
                out.append((sub.lineno, sub.func.attr))
            visit(sub, gated)

    visit(fn, False)
    return out


def _output_violations(fn: ast.AST) -> list:
    """``_slice_out`` calls and subscripts of ``OUT_LEAVES`` attributes
    inside any loop of ``fn``."""
    out = set()
    for loop in ast.walk(fn):
        if not isinstance(loop, LOOPS):
            continue
        for node in ast.walk(loop):
            if isinstance(node, ast.Call):
                f = node.func
                name = (f.attr if isinstance(f, ast.Attribute)
                        else f.id if isinstance(f, ast.Name) else None)
                if name == "_slice_out":
                    out.add((node.lineno, "_slice_out"))
            elif (isinstance(node, ast.Subscript)
                  and isinstance(node.value, ast.Attribute)
                  and node.value.attr in OUT_LEAVES):
                out.add((node.lineno, f"subscript of .{node.value.attr}"))
    return sorted(out)


def check_file(relpath: str, guards) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    functions = _functions(tree)
    errors, checked = [], 0
    base = os.path.basename(relpath)
    for scope, name, banned, gathers in guards:
        fn = functions.get((scope, name))
        qual = ".".join(p for p in (None if scope == "*" else scope, name)
                        if p)
        if fn is None:
            errors.append(f"guarded function {qual} not found in {base} — "
                          "update tools/session_lint.py alongside the "
                          "rename")
            continue
        checked += 1
        for lineno, what in _violations(fn, banned, gathers):
            errors.append(
                f"{base}:{lineno}: {what} in {qual} — "
                + ("the coalesced round path must stage through the "
                   "in-place _HostStager ring buffers, not per-batch "
                   "device ops" if banned is STAGING else
                   "the fused step path must leave row fetches to the "
                   "kernel's scalar-prefetch DMA (ids/timestamps metadata "
                   "only outside the launch)"))
    return checked, errors


def check_fences(relpath: str, guards) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    functions = _functions(tree)
    errors, checked = [], 0
    base = os.path.basename(relpath)
    for scope, name, banned in guards:
        fn = functions.get((scope, name))
        qual = ".".join(p for p in (None if scope == "*" else scope, name)
                        if p)
        if fn is None:
            errors.append(f"guarded function {qual} not found in {base} — "
                          "update tools/session_lint.py alongside the "
                          "rename")
            continue
        checked += 1
        for lineno, what in _fence_violations(fn, banned):
            errors.append(
                f"{base}:{lineno}: unconditional {what} in {qual} — the "
                "round hot path only fences/times inside the sampled-"
                "trace gate (if trace ...:); an every-round sync "
                "serializes the async pipeline")
    return checked, errors


def check_span_sites(relpath: str) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    base = os.path.basename(relpath)
    sites = sum(1 for n in ast.walk(tree) if isinstance(n, ast.With)
                and any(s.startswith(SPAN_PREFIXES) for s in _span_names(n)))
    errors = [f"{base}:{lineno}: unconditional {what} in span {name!r} — "
              "a profiler span site runs on every round; it only fences/"
              "times inside the sampled-trace gate (if trace ...:)"
              for lineno, name, what in _span_site_violations(tree)]
    return sites, errors


def check_faults(relpath: str, guards) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    functions = _functions(tree)
    errors, checked = [], 0
    base = os.path.basename(relpath)
    for scope, name in guards:
        fn = functions.get((scope, name))
        qual = ".".join(p for p in (None if scope == "*" else scope, name)
                        if p)
        if fn is None:
            errors.append(f"guarded function {qual} not found in {base} — "
                          "update tools/session_lint.py alongside the "
                          "rename")
            continue
        checked += 1
        for lineno, what in _fault_violations(fn):
            errors.append(
                f"{base}:{lineno}: ungated fault hook {what}() in {qual} "
                "— injection hooks must sit inside an `if faults ...:` "
                "gate so a disarmed injector costs the hot path nothing")
    return checked, errors


def check_journal(relpath: str, guards) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    functions = _functions(tree)
    errors, checked = [], 0
    base = os.path.basename(relpath)
    for scope, name in guards:
        fn = functions.get((scope, name))
        qual = ".".join(p for p in (None if scope == "*" else scope, name)
                        if p)
        if fn is None:
            errors.append(f"guarded function {qual} not found in {base} — "
                          "update tools/session_lint.py alongside the "
                          "rename")
            continue
        checked += 1
        for lineno, what in _journal_violations(fn):
            errors.append(
                f"{base}:{lineno}: ungated journal hook {what}() in "
                f"{qual} — WAL appends/dedup queries must sit inside an "
                "`if ... journal ...:` gate so a disarmed fleet pays no "
                "disk IO on the ingest hot path")
    return checked, errors


def check_outputs(relpath: str, guards) -> tuple[int, list]:
    with open(os.path.join(REPO, relpath)) as f:
        tree = ast.parse(f.read(), relpath)
    functions = _functions(tree)
    errors, checked = [], 0
    base = os.path.basename(relpath)
    for scope, name in guards:
        fn = functions.get((scope, name))
        qual = f"{scope}.{name}"
        if fn is None:
            errors.append(f"guarded function {qual} not found in {base} — "
                          "update tools/session_lint.py alongside the "
                          "rename")
            continue
        checked += 1
        for lineno, what in _output_violations(fn):
            errors.append(
                f"{base}:{lineno}: {what} in a loop of {qual} — per-tenant "
                "outputs come from one compiled split per cohort "
                "(_split_out), not an eager device program per tenant")
    return checked, errors


def main() -> int:
    errors, checked = [], 0
    for relpath, guards in GUARDED.items():
        c, errs = check_file(relpath, guards)
        checked += c
        errors.extend(errs)
    for relpath, guards in FENCE_GUARDED.items():
        c, errs = check_fences(relpath, guards)
        checked += c
        errors.extend(errs)
    for relpath in SPAN_FILES:
        c, errs = check_span_sites(relpath)
        checked += c
        errors.extend(errs)
    for relpath, guards in FAULT_GUARDED.items():
        c, errs = check_faults(relpath, guards)
        checked += c
        errors.extend(errs)
    for relpath, guards in JOURNAL_GUARDED.items():
        c, errs = check_journal(relpath, guards)
        checked += c
        errors.extend(errs)
    for relpath, guards in OUTPUT_GUARDED.items():
        c, errs = check_outputs(relpath, guards)
        checked += c
        errors.extend(errs)
    for e in errors:
        print(f"session-lint: {e}", file=sys.stderr)
    print(f"session-lint: {checked} hot-path functions and span sites "
          f"checked, "
          f"{len(errors)} error(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
