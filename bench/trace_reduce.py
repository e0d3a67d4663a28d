"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

The trace holds device events only: the harness records it with the
profiler's host tracer off, because the runtime's own host events (one
per chunk of each input transfer's layout change) slow a flush more
than twofold.  So everything here is read off the device planes:

* the window: from the first device operation's start to the last
  one's end (the profile is started just before the harness's window
  and stopped just after it);
* busy time: the union of the intervals in which an operation ran on a
  device plane, averaged over the device planes that ran anything;
* device time by operation name, which for a Pallas kernel is the name
  it was given (``_gemv_kernel`` ...);
* device time by layer family (``conv``, ``dense``), given the kernels
  of each: within one execution of a program (an event of the ``XLA
  Modules`` line), an XLA operation that runs before the first kernel
  (the input's bit-plane preparation) goes to that kernel's family, and
  any other (pad, copy, pooling, the output batch norm) to the family
  of the kernel before it; an operation outside any execution, or in
  one with no kernel, goes to ``other``;
* idle gaps, named ``between executions`` (the host's part of a flush,
  or waiting for the next request) or ``inside an execution`` (the
  device waiting within one program).

    python bench/trace_reduce.py TRACE.xplane.pb   # print its structure
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OTHER = "other"
BETWEEN, INSIDE = "between executions", "inside an execution"
KERNEL = re.compile(r"(_[A-Za-z0-9_]*_kernel)")
SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: dict[str, float]
    op_count: dict[str, int]
    family_s: dict[str, float]             # by layer family, and "other"
    gaps: list[tuple[str, float]]          # (kind, seconds), longest first
    gap_s_by_kind: dict[str, float]
    devices: int


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def op_label(event) -> str:
    """A device op's name: the Pallas kernel's own name where the event
    or its stats carry one (``_gemv_kernel``), else the HLO op's name
    without its numeric suffix (``%fusion.12 = ...`` -> ``fusion``)."""
    m = KERNEL.search(event.name)
    if m:
        return m.group(1)
    for _, v in event.stats:
        if isinstance(v, str):
            m = KERNEL.search(v)
            if m:
                return m.group(1)
    return SUFFIX.sub("", event.name.split(" = ")[0].lstrip("%"))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def device_ops(pd) -> list[tuple[list[tuple[str, float, float]],
                                 list[tuple[float, float]]]]:
    """Per device plane that ran anything: its ops as (name, start, end),
    in order of start, and its program executions as (start, end)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(op_label(e), float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events]
            elif line.name == MODULES_LINE:
                modules += [(float(e.start_ns),
                             float(e.start_ns + e.duration_ns))
                            for e in line.events]
        if ops:
            out.append((sorted(ops, key=lambda o: o[1]), sorted(modules)))
    return out


def op_families(ops: list[tuple[str, float, float]],
                modules: list[tuple[float, float]],
                families: dict[str, list[str]]) -> list[str]:
    """The layer family of each op (in order of start), by the rule in
    the module's docstring."""
    family_of = {k: f for f, ks in families.items() for k in ks}
    module_of = []
    m = 0
    for _, a, _ in ops:
        while m < len(modules) and modules[m][1] < a:
            m += 1
        inside = m < len(modules) and modules[m][0] <= a
        module_of.append(m if inside else None)
    out = [OTHER] * len(ops)
    i = 0
    while i < len(ops):
        j = i + 1
        while j < len(ops) and module_of[j] == module_of[i]:
            j += 1
        if module_of[i] is not None:
            kernels = [family_of[ops[k][0]] for k in range(i, j)
                       if ops[k][0] in family_of]
            current = kernels[0] if kernels else OTHER
            for k in range(i, j):
                current = family_of.get(ops[k][0], current)
                out[k] = current
        i = j
    return out


def _inside(modules: list[tuple[float, float]], t: float) -> bool:
    k = bisect.bisect_right(modules, (t, float("inf"))) - 1
    return k >= 0 and modules[k][0] <= t <= modules[k][1]


def reduce(pd, *, families: dict[str, list[str]] | None = None,
           top_gaps: int = 10) -> Reduced:
    """``families`` maps a layer family to the names of its kernels."""
    planes = device_ops(pd)
    if not planes:
        raise ValueError("the trace holds no device operation")
    lo = min(t for ops, _ in planes for _, t, _ in ops)
    hi = max(e for ops, _ in planes for _, _, e in ops)
    op_s: dict[str, float] = collections.defaultdict(float)
    op_count: dict[str, int] = collections.Counter()
    family_s: dict[str, float] = collections.defaultdict(float)
    busy = 0.0
    gaps: list[tuple[str, float, float]] = []
    for ops, modules in planes:
        fams = op_families(ops, modules, families or {})
        for (n, a, b), f in zip(ops, fams):
            op_s[n] += (b - a) / len(planes) * 1e-9
            op_count[n] += 1
            family_s[f] += (b - a) / len(planes) * 1e-9
        merged = _union([(a, b) for _, a, b in ops])
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(INSIDE if _inside(modules, (a + b) / 2) else BETWEEN, a, b)
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    if busy <= 0:
        raise ValueError("the trace's device operations take no time")
    by_kind: dict[str, float] = collections.defaultdict(float)
    named = []
    for name, a, b in gaps:
        by_kind[name] += (b - a) / len(planes) * 1e-9
        named.append((name, (b - a) * 1e-9))
    named.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy / len(planes) * 1e-9,
                   op_s=dict(op_s), op_count=dict(op_count),
                   family_s=dict(family_s),
                   gaps=named[:top_gaps], gap_s_by_kind=dict(by_kind),
                   devices=len(planes))


def describe(pd, per_line: int = 4) -> None:
    """Print planes, lines, event counts and a few events with stats."""
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            span = ((evs[0].start_ns, evs[-1].start_ns + evs[-1].duration_ns)
                    if evs else ())
            print(f"  LINE {line.name!r} events={len(evs)} span={span}")
            names = collections.Counter(e.name for e in evs)
            print(f"    names: {names.most_common(12)}")
            for e in evs[:per_line]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={list(e.stats)[:8]}")


if __name__ == "__main__":
    data = load(sys.argv[1])
    describe(data)
    r = reduce(data)
    print(dataclasses.asdict(r))
