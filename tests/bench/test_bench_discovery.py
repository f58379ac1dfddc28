"""Configurations, traffic mixes and metric readers are found by name:
adding a file and an entry is enough, no code changes."""
import json
import os

import bench_tiny as BT
from bench.common import harness
from bench.common.readers import View


def test_new_config_and_traffic_are_picked_up(tmp_path):
    root = BT.layout(str(tmp_path), {"tiny-dense": BT.DENSE},
                     {"tiny-chat": BT.OPEN_LOOP},
                     [{"name": "tiny-dense.tiny-chat", "config": "tiny-dense",
                       "traffic": "tiny-chat", "like": "qwen3-4b.chat"}])
    cell = harness.load_cell("tiny-dense.tiny-chat", root)
    assert cell.config["hidden_size"] == 64
    assert cell.mix["arrivals"]["rate_rps"] == 8.0
    assert cell.driver().__name__ == "bench.drivers.open_loop_single"
    assert cell.reference().__name__ == "bench.references.dense_gqa"
    # a second mix and cell: two new files' worth of data, nothing else
    mix = dict(BT.OPEN_LOOP, arrivals={"process": "poisson",
                                       "rate_rps": 3.0})
    with open(os.path.join(root, "bench", "traffic", "tiny-slow.json"),
              "w") as f:
        json.dump(mix, f)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tiny-dense.tiny-slow",
                               "config": "tiny-dense", "traffic": "tiny-slow",
                               "chips": 1, "why": "test"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = harness.load_cell("tiny-dense.tiny-slow", root)
    assert cell.mix["arrivals"]["rate_rps"] == 3.0


def test_new_metric_reader_is_picked_up(tmp_path):
    root = BT.layout(str(tmp_path), {"tiny-dense": BT.DENSE},
                     {"tiny-chat": BT.OPEN_LOOP},
                     [{"name": "c", "config": "tiny-dense",
                       "traffic": "tiny-chat", "like": "qwen3-4b.chat"}])
    with open(os.path.join(root, "bench", "metrics", "answers_n.py"),
              "w") as f:
        f.write("SOURCE = 'program_counter'\n\n\n"
                "def read(v):\n    return len(v.outcome.requests) or None\n")
    reader = harness.metric_reader("answers_n", root)
    assert reader.SOURCE == "program_counter"

    class O:
        requests = [1, 2, 3]
    assert reader.read(View(O(), {}, {}, None)) == 3


def test_every_metric_in_the_benchmark_has_a_reader():
    bench = json.load(open(os.path.join(BT.REPO, "BENCHMARK.json")))
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    for m in bench["per_layer"]:
        r = harness.metric_reader(m["name"])
        assert r.SOURCE == m["source"] and r.SOURCE in sources
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in names
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
