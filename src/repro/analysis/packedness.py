"""Packedness dataflow pass: verify that activations stay bit-packed
across every HBM crossing of a traced packed forward.

Espresso's value proposition — the speedups and the 9.4 MB → 256 KB
intermediate shrink — evaporates silently if one stage leaks an
unpacked int32/float32 activation back to HBM between kernels.  The
per-PR evidence so far was bench-level (``max_intermediate_bytes``
rows); this pass turns it into a machine-checked dataflow invariant.

The pass abstract-interprets a forward's jaxpr (one pass, in trace
order, threading value identity through ``pjit`` call boundaries) and
classifies every value that crosses a ``pallas_call`` boundary — i.e.
is HBM-resident by construction — into:

* ``packed``  — uint32 words (bit-packed activations / weights);
* ``float``   — floating values (folded BN thresholds, attention V,
  the float residual stream of the binary LM, output logits);
* ``unpacked`` — integer non-uint32 values *derived from a kernel
  output* (int32 accumulator activations);
* ``staging`` — integer values derived only from the jaxpr's inputs
  (bit-plane extraction, raw uint8 images) — input staging, not an
  intermediate.

Escape rule (the invariant): a value **produced by a kernel in
unpacked form** must only ever re-enter the kernel domain through a
fused *epilogue* kernel (:data:`EPILOGUE_KERNELS` — the standalone
BN-sign-repack used after accumulating stages, whose whole point is
consuming the int32 bridge).  Reaching any other kernel — e.g. being
host-side re-binarized and fed to the generic ``_bitpack_kernel`` —
is an HBM escape and is reported with producer and consumer names.

Two policies, matching the two workload families:

* ``strict`` (``bcnn`` / ``bmlp``): fully binary networks — every
  kernel output other than packed words is tracked, and the taint
  survives float laundering (an int32 GEMM output that is sign()-ed to
  float and then re-packed is exactly the leak this pass exists for).
* ``float-residual`` (``transformer``, ``reactnet``): the residual
  stream is float by design (the paper's LM serving path; ReActNet's
  float32 stream beside the packed sign), so float kernel outputs are a
  legal class, counted under ``float`` in every report, and an int →
  float conversion ends the taint (the V / Q / K projections are
  *meant* to step through float before re-binarizing).  An int32
  kernel output between binary layers is still an escape.

The headline per-model number is ``max_live_unpacked_bytes``: a
liveness sweep over the unpacked class — the peak HBM footprint of
un-packed activations at any point of the forward.  ``analysis
--check`` pins it (and the full classification) against
``experiments/ANALYSIS_baseline.json``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis import graph

# Kernels whose JOB is to consume an unpacked HBM bridge: the standalone
# fused BN-sign-repack epilogue (used after stages that accumulate in
# int32 outside a single launch — the bit-plane first layers).
EPILOGUE_KERNELS = frozenset({"_bn_sign_pack_kernel"})

POLICIES = ("strict", "float-residual")


@dataclasses.dataclass
class ValueRecord:
    """One traced value (jaxpr-level array) seen by the dataflow walk."""
    shape: tuple[int, ...]
    dtype: str
    nbytes: int
    producer: str                 # 'input' | 'const' | prim or kernel name
    step: int                     # production step (flattened eqn order)
    last_use: int                 # last consuming step (-1: never used)
    kernel_output: bool           # produced directly by a pallas_call
    pallas_ancestry: bool         # transitively derived from a launch
    cls: str = "staging"          # packed | float | unpacked | staging
    escapes: tuple[str, ...] = () # non-epilogue kernels this leaked into


@dataclasses.dataclass(frozen=True)
class Escape:
    """One packedness violation: an unpacked kernel output that crossed
    HBM into a non-epilogue kernel."""
    producer: str
    consumer: str
    shape: tuple[int, ...]
    dtype: str
    nbytes: int

    def describe(self) -> str:
        return (f"{self.producer} -> {self.consumer}: unpacked "
                f"{self.dtype}{list(self.shape)} ({self.nbytes} B) "
                f"crossed HBM outside the epilogue contract")


@dataclasses.dataclass(frozen=True)
class PackednessReport:
    """Result of :func:`analyze_packedness` for one traced forward."""
    policy: str
    launch_count: int
    complete: bool                # dataflow saw every syntactic launch
    hbm_values: dict[str, int]    # class -> count of boundary crossings
    hbm_bytes: dict[str, int]     # class -> max single-value bytes
    max_live_unpacked_bytes: int
    max_unpacked_shape: tuple[int, ...]
    escapes: tuple[Escape, ...]

    @property
    def ok(self) -> bool:
        return not self.escapes and self.complete

    def to_json(self) -> dict[str, Any]:
        """Stable dict form — the ``packedness/*`` baseline cells."""
        return {
            "policy": self.policy,
            "launch_count": self.launch_count,
            "complete": self.complete,
            "hbm_values": dict(sorted(self.hbm_values.items())),
            "hbm_bytes": dict(sorted(self.hbm_bytes.items())),
            "max_live_unpacked_bytes": self.max_live_unpacked_bytes,
            "max_unpacked_shape": list(self.max_unpacked_shape),
            "escapes": [e.describe() for e in self.escapes],
        }


def _is_float(dtype: Any) -> bool:
    return jnp.issubdtype(dtype, jnp.floating)


def _is_packed(dtype: Any) -> bool:
    return jnp.dtype(dtype) == jnp.dtype(jnp.uint32)


def _is_int(dtype: Any) -> bool:
    return jnp.issubdtype(dtype, jnp.integer) or \
        jnp.dtype(dtype) == jnp.dtype(bool)


class _Walker:
    """Abstract interpreter over a closed jaxpr (see module docstring)."""

    def __init__(self, policy: str):
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {policy!r}")
        self.policy = policy
        self.step = 0
        self.values: list[ValueRecord] = []
        self.carries: dict[int, frozenset[int]] = {}   # value idx -> roots
        self.boundary: set[int] = set()                # crossed a launch
        self.launch_count = 0

    # -- value bookkeeping --------------------------------------------------

    def _new(self, aval: Any, producer: str, *,
             kernel_output: bool = False,
             pallas_ancestry: bool = False) -> int:
        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        nbytes = (int(aval.size) * dtype.itemsize
                  if dtype is not None and hasattr(aval, "size") else 0)
        rec = ValueRecord(
            shape=shape, dtype=str(dtype), nbytes=nbytes,
            producer=producer, step=self.step, last_use=-1,
            kernel_output=kernel_output, pallas_ancestry=pallas_ancestry)
        if dtype is None:
            rec.cls = "staging"
        elif _is_packed(dtype):
            rec.cls = "packed"
        elif _is_float(dtype):
            rec.cls = "float"
        elif _is_int(dtype) and pallas_ancestry:
            rec.cls = "unpacked"
        else:
            rec.cls = "staging"
        self.values.append(rec)
        return len(self.values) - 1

    def _tracked_root(self, idx: int) -> bool:
        """Is this value a taint root — a kernel output that left the
        launch in unpacked form?  Under the float-residual policy a
        float kernel output is a legal class, not a root."""
        rec = self.values[idx]
        if not rec.kernel_output or rec.cls == "packed":
            return False
        if rec.cls == "float" and self.policy == "float-residual":
            return False
        return True

    def _propagate(self, out_idx: int, in_idxs: list[int]) -> None:
        rec = self.values[out_idx]
        roots: set[int] = set()
        for i in in_idxs:
            roots |= self.carries.get(i, frozenset())
            if self._tracked_root(i):
                roots.add(i)
        if roots and self.policy == "float-residual" and rec.cls == "float":
            roots = set()          # int -> float conversion launders
        if roots:
            self.carries[out_idx] = frozenset(roots)

    # -- traversal ----------------------------------------------------------

    def run(self, closed: Any) -> None:
        env: dict[Any, int] = {}
        jaxpr = closed.jaxpr
        for var in jaxpr.invars:
            env[var] = self._new(var.aval, "input")
        for var, const in zip(jaxpr.constvars, closed.consts):
            env[var] = self._new(var.aval, "const")
        self._walk(jaxpr, env)
        for var in jaxpr.outvars:
            idx = None if hasattr(var, "val") else env.get(var)
            if idx is not None:
                self.values[idx].last_use = self.step + 1
                self.boundary.add(idx)      # model outputs are HBM-visible

    def _in_idxs(self, eqn: Any, env: dict[Any, int]) -> list[int]:
        idxs = []
        for v in eqn.invars:
            # Literals (unhashable, carry .val) are constants, not values.
            if not hasattr(v, "val") and v in env:
                idxs.append(env[v])
        return idxs

    def _walk(self, jaxpr: Any, env: dict[Any, int]) -> None:
        for eqn in jaxpr.eqns:
            self.step += 1
            in_idxs = self._in_idxs(eqn, env)
            for i in in_idxs:
                self.values[i].last_use = self.step
            name = eqn.primitive.name
            if name == "pallas_call":
                self._visit_pallas(eqn, env, in_idxs)
                continue
            inner = graph.call_subjaxpr(eqn)
            if inner is not None:
                sub_env: dict[Any, int] = {}
                for var, const in zip(inner.jaxpr.constvars, inner.consts):
                    sub_env[var] = self._new(var.aval, "const")
                for var, idx in zip(inner.jaxpr.invars, in_idxs):
                    sub_env[var] = idx
                self._walk(inner.jaxpr, sub_env)
                for outer, var in zip(eqn.outvars, inner.jaxpr.outvars):
                    if not hasattr(var, "val") and var in sub_env:
                        env[outer] = sub_env[var]
                        self.values[sub_env[var]].last_use = self.step
                    else:                    # literal-returning body
                        env[outer] = self._new(outer.aval, name)
                continue
            ancestry = any(self.values[i].pallas_ancestry or
                           self.values[i].kernel_output for i in in_idxs)
            for outer in eqn.outvars:
                idx = self._new(outer.aval, name, pallas_ancestry=ancestry)
                self._propagate(idx, in_idxs)
                env[outer] = idx

    def _visit_pallas(self, eqn: Any, env: dict[Any, int],
                      in_idxs: list[int]) -> None:
        self.launch_count += 1
        kname = graph.kernel_name(eqn)
        for i in in_idxs:
            self.boundary.add(i)
            roots = set(self.carries.get(i, frozenset()))
            if self._tracked_root(i):
                roots.add(i)
            if kname not in EPILOGUE_KERNELS:
                for r in roots:
                    rec = self.values[r]
                    if kname not in rec.escapes:
                        rec.escapes = (*rec.escapes, kname)
        for outer in eqn.outvars:
            idx = self._new(outer.aval, kname, kernel_output=True,
                            pallas_ancestry=True)
            self.boundary.add(idx)
            env[outer] = idx


def _max_live(values: list[ValueRecord]) -> tuple[int, tuple[int, ...]]:
    """Peak concurrent bytes of the unpacked class (linear liveness
    sweep over production/last-use steps) and the single largest
    unpacked value's shape."""
    events: list[tuple[int, int, int]] = []
    best_shape: tuple[int, ...] = ()
    best_bytes = 0
    for rec in values:
        if rec.cls != "unpacked" or rec.last_use < rec.step:
            continue
        events.append((rec.step, 0, rec.nbytes))
        events.append((rec.last_use, 1, -rec.nbytes))
        if rec.nbytes > best_bytes:
            best_bytes, best_shape = rec.nbytes, rec.shape
    live = peak = 0
    # births sort before deaths at the same step: a value is live at the
    # step that both produces it and last-uses its predecessor.
    for _, _, delta in sorted(events):
        live += delta
        peak = max(peak, live)
    return peak, best_shape


def analyze_packedness(fn: Any, *args: Any,
                       policy: str = "strict") -> PackednessReport:
    """Run the packedness dataflow pass over ``fn`` traced at ``args``.

    Pure tracing (``jax.make_jaxpr``) — no kernel executes, so the
    pallas backend is cheap to analyze off-TPU.  ``policy``:
    ``'strict'`` (fully binary networks) or ``'float-residual'``
    (binary LMs with a float residual stream); see module docstring.
    """
    closed = jax.make_jaxpr(fn)(*args)
    walker = _Walker(policy)
    walker.run(closed)

    syntactic = sum(1 for eqn in graph.iter_eqns(closed.jaxpr)
                    if eqn.primitive.name == "pallas_call")
    hbm_values: dict[str, int] = {}
    hbm_bytes: dict[str, int] = {}
    escapes: list[Escape] = []
    for idx, rec in enumerate(walker.values):
        if idx in walker.boundary:
            hbm_values[rec.cls] = hbm_values.get(rec.cls, 0) + 1
            hbm_bytes[rec.cls] = max(hbm_bytes.get(rec.cls, 0), rec.nbytes)
        for kname in rec.escapes:
            escapes.append(Escape(producer=rec.producer, consumer=kname,
                                  shape=rec.shape, dtype=rec.dtype,
                                  nbytes=rec.nbytes))
    peak, shape = _max_live(walker.values)
    return PackednessReport(
        policy=policy,
        launch_count=walker.launch_count,
        complete=walker.launch_count == syntactic,
        hbm_values=hbm_values,
        hbm_bytes=hbm_bytes,
        max_live_unpacked_bytes=peak,
        max_unpacked_shape=shape,
        escapes=tuple(sorted(escapes,
                             key=lambda e: (e.producer, e.consumer))),
    )


def model_policy(kind: str) -> str:
    """The packedness policy each workload family is verified under."""
    return ("float-residual" if kind in ("transformer", "reactnet")
            else "strict")
