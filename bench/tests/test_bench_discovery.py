"""A later change adds a configuration, a traffic mix or a per-layer
metric as new files and new entries in ``BENCHMARK.json``, and edits no
file the benchmark already has: a copy of the benchmark with one of each
added runs the new cell and reports the new metric; and a copy with a
GDELT-shaped configuration (static node features, no edge features, one
population of actors) added runs that as a cell of its own."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

DRIVER = """
import json, sys, time
sys.path.insert(0, sys.argv[1])
import harness
spec = harness.load_spec(sys.argv[2])
rec = harness.run_cell(spec, sys.argv[3], 3, 0.3, False, time.monotonic(),
                       lambda m: None)
# a CPU has no published peaks: the v5e's stand in, so that every reader
# that needs no trace reads
rec.setdefault("peaks", harness.peaks.peaks("TPU v5 lite"))
print(json.dumps({"correct": rec["correct"], "errors": rec["errors"],
                  "e2e": harness.end_to_end(spec, rec),
                  "layer": harness.per_layer(spec, rec)}))
"""


def _copy(tmp_path):
    """A copy of the benchmark, and the bytes of every file it has."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return {p: open(os.path.join(dp, p), "rb").read()
            for dp, _d, fs in os.walk(tmp_path / "bench") for p in fs}


def _run(tmp_path, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", DRIVER,
                        str(tmp_path / "bench"), str(tmp_path), cell],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _unedited(tmp_path, before):
    """Nothing the benchmark already had was edited."""
    for p_, data in before.items():
        found = [os.path.join(dp, p_) for dp, _d, fs in
                 os.walk(tmp_path / "bench") if p_ in fs]
        assert any(open(f, "rb").read() == data for f in found)


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    before = _copy(tmp_path)
    # a configuration, a traffic mix and a per-layer metric, as new files
    cfg = json.load(open(tmp_path / "bench/configs/tgn-wikipedia.json"))
    cfg.update(name="tgn-tiny", batch=8, lanes=[dict(cfg["lanes"][0],
                                                     count=1)])
    cfg["graph"].update(n_users=30, n_items=20, n_edges=500)
    cfg["widths"].update(f_mem=8, f_time=8, f_emb=8)
    json.dump(cfg, open(tmp_path / "bench/configs/tgn-tiny.json", "w"))
    json.dump({"loop": "closed", "warmup_rounds": 1},
              open(tmp_path / "bench/traffic/short.json", "w"))
    (tmp_path / "bench/metrics/test.window_rounds.py").write_text(
        "def read(rec):\n    return float(rec['window_rounds'])\n")
    # and their entries
    spec = json.load(open(tmp_path / "BENCHMARK.json"))
    spec["configs"].append({"name": "tgn-tiny", "source": "test",
                            "file": "bench/configs/tgn-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-short", "config": "tgn-tiny",
                              "traffic": "short", "chips": 1, "why": "t"})
    for m in spec["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("tiny-short")
    spec["per_layer"].append({"name": "test.window_rounds", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "events_per_s",
                              "workloads": ["tiny-short"]})
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))

    out = _run(tmp_path, "tiny-short")
    assert out["correct"]
    assert set(out["e2e"]) == {"events_per_s", "setup_s"}
    assert out["layer"]["test.window_rounds"]["value"] > 0
    _unedited(tmp_path, before)


def test_a_gdelt_shaped_configuration_is_new_files_only(tmp_path):
    before = _copy(tmp_path)
    # one population of 100 actors, 8-d node features, no edge features;
    # a staged student lane and an XLA teacher lane
    cfg = json.load(open(tmp_path / "bench/configs/tgn-wikipedia.json"))
    cfg.update(name="tgn-actors", batch=8, lanes=[
        {"name": "np4-staged", "variant": "sat+lut+np4", "tier": "staged",
         "params": "student", "k": 4, "sampler": "recent", "count": 1},
        {"name": "teacher-ref", "variant": "vanilla+cosine", "tier": "ref",
         "params": "teacher", "k": 10, "sampler": "recent", "count": 1}])
    cfg["graph"].update(n_users=100, n_items=0, n_edges=500, f_edge=0,
                        f_feat=8, bipartite=False)
    cfg["widths"].update(f_mem=8, f_time=8, f_emb=8)
    json.dump(cfg, open(tmp_path / "bench/configs/tgn-actors.json", "w"))
    spec = json.load(open(tmp_path / "BENCHMARK.json"))
    spec["configs"].append({"name": "tgn-actors", "source": "test",
                            "file": "bench/configs/tgn-actors.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "actors-backlog",
                              "config": "tgn-actors", "traffic": "backlog",
                              "chips": 1, "why": "t"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("actors-backlog")
    json.dump(spec, open(tmp_path / "BENCHMARK.json", "w"))

    out = _run(tmp_path, "actors-backlog")
    assert out["correct"], out["errors"]
    assert set(out["errors"]) == {"np4-staged", "teacher-ref"}
    assert set(out["e2e"]) == {"events_per_s", "setup_s"}
    assert out["e2e"]["events_per_s"]["value"] > 0
    # the readers that need no trace: the session's host step, and the
    # whole step's share of the peak from the counts with W_s f in them
    assert set(out["layer"]) == {"session.host_step_ms.backlog", "step_mfu"}
    assert all(v["value"] > 0 for v in out["layer"].values())
    _unedited(tmp_path, before)
