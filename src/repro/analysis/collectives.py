"""Sharding pass: collective-traffic analysis of compiled (partitioned)
HLO, lifted out of ``distributed/verify_sharded.py``'s inline asserts.

The sharded packed forward has exactly two legal collective shapes
(DESIGN.md / ``distributed/sharding.py``):

* **data-parallel mesh** (model degree 1): ZERO collectives anywhere in
  the forward — batch shards never communicate;
* **model-parallel mesh**: packed-word **all-gathers only** — an
  all-reduce would mean a contraction crossed chips with a partial
  int32 sum, and a reduce-scatter / all-to-all would mean the shard
  plan resharded an activation mid-stack.

``utils/hlo.py`` stays the low-level text parser (regex + wire-byte
model); this module turns its output into reusable verdicts with a
violation list, so the verifier and the merged analysis report both
apply the SAME rule instead of hand-rolled copies of
``set(kinds) <= {...}``.
"""
from __future__ import annotations

import dataclasses

from repro.utils.hlo import collective_bytes, collective_kinds

# The one collective a model-parallel packed forward may emit: the
# packed-word all-gather at stage output seams (``cnn._gather_packed``).
MODEL_PARALLEL_ALLOWED = frozenset({"all-gather"})


@dataclasses.dataclass(frozen=True)
class CollectiveReport:
    """Collective inventory of one compiled module + rule verdicts."""
    kinds: dict[str, int]            # kind -> occurrence count
    bytes_by_kind: dict[str, float]  # kind -> modeled wire bytes
    total_bytes: float
    violations: tuple[str, ...]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "kinds": dict(sorted(self.kinds.items())),
            "total_bytes": self.total_bytes,
            "violations": list(self.violations),
        }


def analyze_hlo(hlo_text: str) -> tuple[dict[str, int], dict[str, float]]:
    """Raw collective inventory (kinds + modeled bytes) of one module."""
    by_kind = collective_bytes(hlo_text)
    return collective_kinds(hlo_text), by_kind


def check_data_parallel(hlo_text: str) -> CollectiveReport:
    """Data-parallel rule: the partitioned module must contain ZERO
    collectives — any at all means batch shards are communicating."""
    kinds, by_kind = analyze_hlo(hlo_text)
    total = float(by_kind.get("total", 0.0))
    violations = tuple(
        f"data-parallel path emits {n}x {kind} "
        f"({by_kind.get(kind, 0.0):.0f} B) — must be collective-free"
        for kind, n in sorted(kinds.items()))
    if not kinds and total:
        violations = (f"data-parallel path moves {total:.0f} collective "
                      "bytes — must be collective-free",)
    return CollectiveReport(kinds=kinds, bytes_by_kind=by_kind,
                            total_bytes=total, violations=violations)


def check_model_parallel(hlo_text: str, *,
                         allowed: frozenset[str] = MODEL_PARALLEL_ALLOWED
                         ) -> CollectiveReport:
    """Model-parallel rule: only ``allowed`` collective kinds (default:
    the packed-word all-gather).  An all-reduce is the canonical
    violation — a partial int32 sum crossed chips unpacked."""
    kinds, by_kind = analyze_hlo(hlo_text)
    violations = tuple(
        f"off-plan collective: {n}x {kind} "
        f"({by_kind.get(kind, 0.0):.0f} B) — model mesh allows only "
        f"{sorted(allowed)}"
        for kind, n in sorted(kinds.items()) if kind not in allowed)
    return CollectiveReport(kinds=kinds, bytes_by_kind=by_kind,
                            total_bytes=float(by_kind.get("total", 0.0)),
                            violations=violations)


def check_mesh(hlo_text: str, mesh_shape: tuple[int, int]
               ) -> CollectiveReport:
    """Apply the rule matching a (data, model) mesh shape: model degree
    1 is the data-parallel rule, anything else the model-parallel one."""
    if mesh_shape[1] == 1:
        return check_data_parallel(hlo_text)
    return check_model_parallel(hlo_text)
