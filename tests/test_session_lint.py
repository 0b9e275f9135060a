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
