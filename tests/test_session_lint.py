"""``tools/session_lint.py`` rule 3 over profiler spans: the span helper
and every ``session.*``/``frontend.*`` span site stay fence-free outside
the sampled-trace gate, with the two probes each span holds by design."""
from __future__ import annotations

import ast
import importlib.util
import os
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lint():
    path = os.path.join(REPO, "tools", "session_lint.py")
    spec = importlib.util.spec_from_file_location("session_lint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tree_passes(lint, capsys):
    assert lint.main() == 0
    assert "0 error(s)" in capsys.readouterr().out


@pytest.mark.parametrize("body, probes", [
    # the reuse gate's fence is allowed in its own span only
    ('with span("session.stage_wait"):\n    jax.block_until_ready(x)', []),
    ('with span("session.stage"):\n    jax.block_until_ready(x)',
     ["block_until_ready"]),
    # step's round-wall timing pair is allowed; a fence is not
    ('with span("session.step", round=r):\n    t0 = time.perf_counter()',
     []),
    ('with span("session.step", round=r):\n    jax.block_until_ready(s)',
     ["block_until_ready"]),
    ('with span("session.dispatch", trace):\n    t = time.perf_counter()',
     ["perf_counter"]),
    ('with obs.span("frontend.flush", trace):\n    t = time.perf_counter()',
     ["perf_counter"]),
    # inside the sampled-trace gate anything goes
    ('with span("session.outputs"):\n    if trace is not None:\n'
     '        jax.block_until_ready(x)', []),
    # a stage_wait nested in another span keeps its allowance only
    ('with span("session.stage"):\n    with span("session.stage_wait"):\n'
     '        jax.block_until_ready(x)\n    time.perf_counter()',
     ["perf_counter"]),
    # other spans are not this rule's business
    ('with span("bench.step"):\n    jax.block_until_ready(x)', []),
])
def test_span_sites(lint, body, probes):
    tree = ast.parse(textwrap.dedent(body))
    found = [what for _line, _name, what in lint._span_site_violations(tree)]
    assert found == probes


def test_helper_may_not_fence(lint):
    src = ("def span(name, trace=None, **args):\n"
           "    jax.block_until_ready(args)\n"
           "    return TraceAnnotation(name, **args)\n")
    (fn,) = ast.parse(src).body
    assert [w for _l, w in lint._fence_violations(fn, lint.FENCES)] == [
        "block_until_ready"]


# rule 6: ``_coalesced_round``'s loops hand out per-tenant outputs from
# one compiled split per cohort, never one eager program per tenant
_ROUND = "def _coalesced_round(self, batches):\n{}"


@pytest.mark.parametrize("body, found", [
    # the loop as it was: one _slice_out (five eager index ops) a tenant
    ("    for c, out in zip(cohorts, outs_t):\n"
     "        c.state = out.state\n"
     "        for i, tid in enumerate(c.tids):\n"
     "            if tid in host:\n"
     "                outs[tid] = self._slice_out(out, i, b)\n",
     ["_slice_out"]),
    # the same cut inlined: a subscript of a round output's leaf
    ("    for c, out in zip(cohorts, outs_t):\n"
     "        outs.update({t: BatchOut(None, out.emb_src[i], out.nbr_dt[i])\n"
     "                     for i, t in enumerate(c.tids)})\n",
     ["subscript of .emb_src", "subscript of .nbr_dt"]),
    # the loop as it is: one split a cohort, host-side picks of its slots
    ("    for c, out in zip(cohorts, outs_t):\n"
     "        c.state = out.state\n"
     "        slots = _split_out(tuple(getattr(out, f)\n"
     "                                 for f in _OUT_LEAVES))\n"
     "        for i, tid in mine:\n"
     "            leaves = slots[i]\n"
     "            if b < widths[id(c)]:\n"
     "                leaves = _trim_out(b, leaves)\n"
     "            outs[tid] = tgn.BatchOut(None, *leaves)\n",
     []),
    # outside a loop a subscript is no per-tenant cut
    ("    first = out.emb_src[0]\n", []),
])
def test_output_rule(lint, body, found):
    (fn,) = ast.parse(_ROUND.format(body)).body
    assert [w for _l, w in lint._output_violations(fn)] == found


def test_output_rule_guards_the_round(lint, tmp_path, monkeypatch):
    """The rule is wired to ``SessionManager._coalesced_round`` and fails
    the run when the guarded function disappears."""
    rel = next(iter(lint.OUTPUT_GUARDED))
    assert lint.check_outputs(rel, lint.OUTPUT_GUARDED[rel]) == (1, [])
    (tmp_path / "s.py").write_text("class SessionManager:\n    pass\n")
    monkeypatch.setattr(lint, "REPO", str(tmp_path))
    checked, errors = lint.check_outputs(
        "s.py", (("SessionManager", "_coalesced_round"),))
    assert checked == 0 and "not found" in errors[0]
