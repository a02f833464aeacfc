"""KernelPlanTable — the What/When/Where verdicts as a jit-static routing
table.

`ServeSession.kernel_plan` produces one planner `Decision` per decode GEMM;
this module freezes those verdicts into a hashable structure the model
stack can close a `jax.jit` over.  Because the table is Python-static, the
gate is resolved at trace time: a gated label lowers to the weight-
stationary INT8 Pallas kernel, an ungated one to the plain XLA matmul, and
the compiled decode executable never branches (one lowered program, no
per-token retrace).

Labels are the *short* projection names ("Wq", "mlp-down", "ssm-BCdt",
"lm_head", ...) — the `gemms_of_model` labels with the model-name prefix
stripped — so the table is independent of which config produced it.
Lookup of a label the planner never saw raises `KeyError` (listing the
known labels): model-side label drift must not silently disable gating.

A serving core builds one table per phase (decode and prefill) before it
jits and never changes them; equality and hashing are by content (the
entries are kept sorted), so `DecodeCore.batch_step_for` keeps one
compiled step per distinct table.  `flips(other)` diffs two tables by
their "when" gate — the core compiles a separate prefill program only
when a projection label flips between the phases.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class PlanEntry:
    """One label's verdict: the paper's when (use_cim), what and where."""
    use_cim: bool
    what: str = "baseline"
    where: str = "PE"


def strip_model_prefix(label: str, model_name: str = "") -> str:
    prefix = f"{model_name} "
    return label[len(prefix):] if model_name and label.startswith(prefix) \
        else label


@dataclasses.dataclass(frozen=True)
class KernelPlanTable:
    """Hashable label -> PlanEntry map (valid as a jit-static closure)."""
    entries: tuple[tuple[str, PlanEntry], ...] = ()

    @classmethod
    def from_decisions(cls, decisions: Iterable, model_name: str = ""
                       ) -> "KernelPlanTable":
        """Build from planner Decisions (e.g. ServeSession.kernel_plan
        values); `model_name` strips the `gemms_of_model` label prefix."""
        rows = []
        for d in decisions:
            lab = strip_model_prefix(d.gemm.label, model_name)
            rows.append((lab, PlanEntry(use_cim=bool(d.use_cim),
                                        what=d.what, where=d.where)))
        return cls(entries=tuple(sorted(rows)))

    @cached_property
    def _index(self) -> dict:
        return dict(self.entries)

    @property
    def labels(self) -> tuple:
        return tuple(lab for lab, _ in self.entries)

    def entry(self, label: str) -> PlanEntry:
        try:
            return self._index[label]
        except KeyError:
            raise KeyError(
                f"unknown GEMM label {label!r}: not in the kernel plan "
                f"(known labels: {sorted(self._index)})") from None

    def use_cim(self, label: str) -> bool:
        """The planner's "when" gate for one projection label.  Raises
        KeyError on labels absent from the plan — a renamed model-side
        projection must fail loudly, not silently run ungated."""
        return self.entry(label).use_cim

    def ungated(self) -> "KernelPlanTable":
        """Copy with every gate forced off (the parity-baseline program:
        identical labels and quantized weights, all-standard routing)."""
        return KernelPlanTable(entries=tuple(
            (lab, dataclasses.replace(e, use_cim=False))
            for lab, e in self.entries))

    def flips(self, other: "KernelPlanTable") -> tuple[str, ...]:
        """Labels whose "when" gate (use_cim) differs between the two
        tables; a label present in only one table counts as flipped."""
        labels = set(self._index) | set(other._index)
        out = []
        for lab in sorted(labels):
            a, b = self._index.get(lab), other._index.get(lab)
            if a is None or b is None or a.use_cim != b.use_cim:
                out.append(lab)
        return tuple(out)
