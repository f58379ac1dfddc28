"""What a driver hands back to ``run.py``."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Outcome:
    requests: List[Any]                 # every request offered in the window
    attempted: int
    failed: int
    setup_s: float                      # process start → first offered request
    window_s: float                     # the measured window
    window_t0: float                    # its start, host monotonic clock
    t_done: float = 0.0                 # end of the drain, same clock
    max_batch: int = 0
    engine_stats: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)           # engine name → EngineStats, at close
    transfer_stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    records: List[Any] = dataclasses.field(default_factory=list)
    trace_dir: Optional[str] = None
    trace_window: Optional[tuple] = None    # (t0, t1) host perf_counter
    compiles_in_window: int = 0
    memory_peak_bytes: Optional[int] = None
    generator_lag_s: float = 0.0        # how late the load generator ran
    notes: List[str] = dataclasses.field(default_factory=list)
    # frees the program's device state before the reference runs
    release: Callable[[], None] = lambda: None
    spec: Any = None                    # weight spec, for the reference
    samples: List[Any] = dataclasses.field(default_factory=list)
    # the D engine's logits for each sampled request's decode tokens
    decode_logits: Dict[str, List[Any]] = dataclasses.field(
        default_factory=dict)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)
