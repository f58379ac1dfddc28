"""Discovery by name, and what every run shares.

``BENCHMARK.json`` names each piece; the file that holds it is found from
that name alone, so a later change adds a configuration, a traffic mix, a
driver, a reference or a metric by adding a file and an entry:

  configuration  the ``file`` of its ``configs`` entry
  traffic mix    ``bench/traffic/<traffic>.json``
  driver         ``bench/drivers/<driver>.py``, named by the mix
  reference      ``bench/references/<reference>.py``, named by the config
  metric reader  ``bench/metrics/<metric name>.py``
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: Optional[str] = None):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = name or "bench_dyn_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's content
    config_name: str
    traffic_name: str
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def driver(self):
        return importlib.import_module("bench.drivers." + self.mix["driver"])

    def reference(self):
        return importlib.import_module("bench.references."
                                       + self.config["reference"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[str] = None) -> Cell:
    root = root or ROOT
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = read_json(os.path.join(root, confs[w["config"]]["file"]))
    mix = read_json(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(name, int(w["chips"]), conf, w["config"], w["traffic"], mix,
                e2e, per_layer)


def metric_reader(name: str, root: Optional[str] = None):
    root = root or ROOT
    return load_module(os.path.join(root, "bench", "metrics", name + ".py"))


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and backend
    compiles (copied from ``chip_smoke.CompileClock``)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import logging
        import time

        import jax
        self.seconds = 0.0
        self.programs = 0
        self.names: List[tuple] = []        # (host monotonic, program)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        # program names, from JAX's compile log, kept off stderr
        jax.config.update("jax_log_compiles", True)
        names = self.names

        class _Names(logging.Handler):
            def emit(self, rec):
                msg = rec.getMessage()
                if msg.startswith("Compiling "):
                    names.append((time.monotonic(), msg.split(" ")[1]))

        for lg in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            logger = logging.getLogger(lg)
            logger.addHandler(_Names())
            logger.propagate = False
            logger.setLevel(logging.WARNING)

    def names_since(self, t: float) -> List[str]:
        return [n for tt, n in self.names if tt >= t]

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]

    def snapshot(self):
        return self.seconds, self.programs


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> Dict[str, Any]:
    """Refuse every backend but the TPU, and fewer chips than the cell
    asks for (as ``chip_smoke._require_tpu`` does)."""
    dev = device_info()
    if dev["platform"] != "tpu":
        sys.exit(f"bench: JAX found no TPU (backend {dev['platform']!r}); "
                 f"no result")
    if dev["count"] < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{dev['count']}; no result")
    return dev


def peak_memory_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
