"""The chip smoke's served path and reference check, rehearsed on the CPU.

``chip_smoke.py`` drives the served path at Wikipedia scale on a TPU and
refuses to run anywhere else; its phases are plain functions, so here they
run at a tiny size with the Pallas kernels in interpret mode. Every PR
rehearses what the chip run will do.
"""
import importlib.util
import os

import jax
import pytest

from repro.data import temporal_graph as tgd
from repro.utils import CHECKOUT, use_compile_cache

TINY = dict(f_mem=16, f_time=16, f_emb=16, m_r=10)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(CHECKOUT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_graph():
    return tgd.generate(tgd.StreamConfig(n_users=60, n_items=40,
                                         n_edges=400, f_edge=172))


def test_served_path_matches_reference(smoke, tiny_graph):
    lines = []
    rep = smoke.served_phase(tiny_graph, TINY, rounds=2, batch=16,
                             log=lines.append)
    assert rep["tiers"] == {"np4-fused": "fused",
                            "np4-reservoir-fused": "fused",
                            "np2-staged": "staged", "teacher-ref": "ref"}
    assert rep["counters"]["round_traces"] == 1
    assert rep["launches"] == [1]
    # fused lanes launch once per step, the staged lane three times
    assert rep["n_launch"] == 1 + 1 + 3
    assert rep["n_custom"] == 0          # interpret mode: no Mosaic call
    assert rep["stats"]["rounds"] == 2
    assert rep["stats"]["accepted"] == 4 * 2 * 16
    # float32 on both sides here: far inside the chip's tolerances
    for name, errs in rep["errors"].items():
        assert errs["memory_abs"] < 1e-5, name
        assert errs["emb_rel"] < 1e-5, name
    assert smoke.check_served(rep) == [
        "no tpu_custom_call in the compiled round"]
    assert any(line.startswith("lane teacher-ref [ref]") for line in lines)


def test_check_served_names_each_failure(smoke):
    rep = {"tiers": {n: t for n, _v, t, _p in smoke.LANES},
           "counters": {"round_traces": 2}, "launches": [1, 3],
           "n_custom": 5, "lanes_ok": {"np2-staged": False}}
    assert smoke.check_served(rep) == ["round_traces 2",
                                       "launches per round [1, 3]",
                                       "lane np2-staged out of tolerance"]


def test_main_refuses_a_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "platform 'cpu'" in captured.err
    assert captured.out == ""


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
