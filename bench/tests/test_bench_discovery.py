"""A later change adds a configuration, a traffic mix or a per-layer
metric as new files and new entries in ``BENCHMARK.json``, and edits no
file the benchmark already has: a copy of the benchmark with one of each
added runs the new cell and reports the new metric."""
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
rec = harness.run_cell(spec, "tiny-short", 3, 0.3, False, time.monotonic(),
                       lambda m: None)
print(json.dumps({"correct": rec["correct"],
                  "e2e": harness.end_to_end(spec, rec),
                  "layer": harness.per_layer(spec, rec)}))
"""


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _d, fs in os.walk(tmp_path / "bench") for p in fs}
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

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", DRIVER,
                        str(tmp_path / "bench"), str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"]
    assert set(out["e2e"]) == {"events_per_s", "setup_s"}
    assert out["layer"]["test.window_rounds"]["value"] > 0
    # nothing the benchmark already had was edited
    for p_, data in before.items():
        found = [os.path.join(dp, p_) for dp, _d, fs in
                 os.walk(tmp_path / "bench") if p_ in fs]
        assert any(open(f, "rb").read() == data for f in found)
