"""Configurations, mixes and metrics are found by name: adding one is
adding files and BENCHMARK.json entries, with no edit to any file."""

import json

from conftest import make_tiny_bench, run_cell

EXTRA_METRIC = '''
def read(r):
    return 42.0 + r.result["attempted"] * 0
'''


def add_parts(root):
    """A new configuration, a new mix and a new metric, as new files."""
    base = root / "bench"
    lm = json.loads((base / "configs" / "tiny-lm.json").read_text())
    lm["name"] = "tiny-lm-wide"
    lm["model"] = dict(lm["model"], d_ff=192)
    (base / "configs" / "tiny-lm-wide.json").write_text(json.dumps(lm))
    mix = json.loads((base / "traffic" / "tiny-serve.json").read_text())
    mix["arrival"] = {"process": "poisson", "rate_per_s": 2.0}
    (base / "traffic" / "tiny-slow.json").write_text(json.dumps(mix))
    (base / "metrics" / "tiny_answer.py").write_text(EXTRA_METRIC)
    (base / "limits" / "tiny-wide-slow.json").write_text(
        json.dumps({"logit_gap": 1e-3}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "tiny-wide-slow", "config": "tiny-lm-wide",
         "traffic": "tiny-slow", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "tiny-serve" in m["workloads"]:
            m["workloads"].append("tiny-wide-slow")
    bench["per_layer"].append(
        {"name": "tiny_answer", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "engine",
         "moves": "itl_p95_ms", "workloads": ["tiny-wide-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_parts_are_found(tmp_path):
    bench = make_tiny_bench(tmp_path)
    add_parts(tmp_path)
    out = run_cell(bench, "tiny-wide-slow", seconds=2)
    line = out["line"]
    assert line["correct"] is True
    assert {"itl_p95_ms", "setup_s",
            "serve_gen_tokens_per_s"} <= set(line["metrics"])
    assert line["attempted"] == 4        # 2 requests/s over 2 s
    traced = run_cell(bench, "tiny-wide-slow", seconds=2, trace=1)
    assert traced["line"]["metrics"]["tiny_answer"]["value"] == 42.0
    # a metric listed for other cells is not read here
    assert "mfu.train" not in traced["line"]["metrics"]
