"""What a metric reader sees: the window, the harness's spans and the
engine's counters at its edges, the engine's record of each launch with the
architecture's cost of it, and the reduced trace. Readers live in
``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``;
each defines ``read(ctx)`` and returns a number, or None when the cell gives
it nothing to read.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from harness.peaks import peaks_for


@dataclasses.dataclass
class Context:
    spec: object  # harness.cell.Spec
    arch: object  # the architecture's Arch (bench/architectures/<name>.py)
    driver: object  # harness.driver.Driver
    device_kind: str
    t0: float  # window (or traced window) start, host monotonic seconds
    t1: float
    stats0: dict  # engine.stats at t0
    stats1: dict  # engine.stats at t1
    architecture: object = None  # the module bench/architectures/<name>.py
    trace: Optional[object] = None  # harness.trace.Summary
    censor: Optional[float] = None  # when late first tokens stopped being awaited
    setup_s: Optional[float] = None

    @property
    def peaks(self) -> dict:
        return peaks_for(self.device_kind)

    @property
    def engine_conf(self) -> dict:
        return self.spec.mix["engine"]

    def delta(self, key: str) -> float:
        return self.stats1[key] - self.stats0[key]

    def steps(self) -> list:
        """Harness spans of engine steps inside the window."""
        return self.driver.steps_in(self.t0, self.t1)

    def launches(self) -> list:
        """The engine's ``StepRecord`` of each ragged launch in the window
        (``engine.step_records``), in order."""
        recs = [r for r in self.driver.engine.step_records
                if r.slot is not None and self.t0 <= r.t0 and r.t1 <= self.t1]
        if len(recs) != self.delta("decode_steps"):
            raise RuntimeError(f"engine.step_records holds {len(recs)} of the window's "
                               f"{self.delta('decode_steps')} launches")
        return recs

    def launch_costs(self) -> list:
        """Per launch in the window: the architecture's {"linear",
        "attention", "head"} Costs of the rows that carry a token, summed
        over layers."""
        b = self.engine_conf["batch_slots"]
        return [self.architecture.launch_cost(self.arch, r, b) for r in self.launches()]
