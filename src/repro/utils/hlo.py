"""Compiled-HLO text inspection: collective ops and their wire bytes.

The parser under ``analysis.collectives``, whose rules the sharded
packed-forward verifier (`distributed/verify_sharded.py`) and the
analysis report's sharding cells apply.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire-byte model from the partitioned module:

    all-gather / all-to-all / collective-permute: output bytes;
    reduce-scatter: input bytes ~= output * k (approximated by output
    bytes of the pre-scatter operand — we use output*1 as lower bound,
    noted); all-reduce: 2x bytes (reduce-scatter + all-gather ring)."""
    per_kind: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        ty, kind = m.group(1), m.group(2)
        b = _shape_bytes(ty)
        factor = 2.0 if kind == "all-reduce" else 1.0
        per_kind[kind] = per_kind.get(kind, 0.0) + b * factor
    per_kind["total"] = sum(v for k, v in per_kind.items())
    return per_kind


def collective_kinds(hlo_text: str) -> dict[str, int]:
    """Occurrence count per collective kind in the partitioned module."""
    kinds: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kinds[m.group(2)] = kinds.get(m.group(2), 0) + 1
    return kinds
