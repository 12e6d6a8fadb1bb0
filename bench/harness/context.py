"""What a metric reader sees: the window, the harness's spans and the
engine's counters at its edges, the row metadata of each launch, and the
reduced trace. Readers live in ``bench/end_to_end/<metric>.py`` and
``bench/layer_metrics/<metric>.py``; each defines ``read(ctx)`` and returns a
number, or None when the cell gives it nothing to read.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from harness import costs
from harness.peaks import peaks_for


@dataclasses.dataclass
class Context:
    spec: object  # harness.cell.Spec
    arch: object  # harness.model.Arch
    driver: object  # harness.driver.Driver
    device_kind: str
    t0: float  # window (or traced window) start, host monotonic seconds
    t1: float
    stats0: dict  # engine.stats at t0
    stats1: dict  # engine.stats at t1
    launches: list = dataclasses.field(default_factory=list)  # (t, slot, pos, ctx, logit_idx)
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

    def launch_costs(self) -> list:
        """Per launch in the window: {"linear", "attention", "head"} Costs
        of the rows that carry a token, summed over layers."""
        a = self.arch
        lin = a.linears()
        out = []
        b = self.engine_conf["batch_slots"]
        for _, slot, pos, ctx, logit_idx in self.launches:
            real = slot < b
            m = int(real.sum())
            seg = {name: (n, a.rank_of(k, n), min(a.group, a.rank_of(k, n)))
                   for name, (_, k, n, _) in lin.items()}
            d, f = a.d_model, a.d_ff
            layer = (costs.dual_gemm(m, d, [seg["q"], seg["k"], seg["v"]], a.group)
                     + costs.dual_gemm(m, a.n_heads * a.head_dim, [seg["o"]], a.group)
                     + costs.dual_gemm(m, d, [seg["gate"], seg["up"]], a.group)
                     + costs.dual_gemm(m, f, [seg["down"]], a.group))
            slots = sorted(set(int(s) for s in slot[real]))
            att = costs.ragged_attention((pos[real] + 1).tolist(),
                                         [int(ctx[s]) for s in slots],
                                         a.n_heads, a.n_kv_heads, a.head_dim)
            used = sum(1 for s in slots if int(slot[int(logit_idx[s])]) == s)
            head = costs.lm_head(used, d, a.vocab)
            out.append({
                "linear": costs.Cost(layer.ops * a.n_layers, layer.bytes * a.n_layers),
                "attention": costs.Cost(att.ops * a.n_layers, att.bytes * a.n_layers),
                "head": head,
            })
        return out
