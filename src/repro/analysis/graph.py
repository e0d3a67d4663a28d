"""Traced-program graph core: the ONE jaxpr traversal every static
pass is built on.

The kernel subsystem's evidence ("the bit-plane conv is ONE launch",
"the patch matrix never hits HBM") is op-count-level: it comes from
walking a traced jaxpr, recursing into nested (pjit) bodies.  ONE
recursive traversal (:func:`iter_eqns`) backs every consumer — the
:func:`pallas_launches` launch inventory (kernel name + grid per
launch), the :func:`pallas_grids` / :func:`count_pallas_calls` views
over it, :func:`max_intermediate_bytes` (the largest HBM intermediate,
the fused-epilogue evidence), and the dataflow passes in
``analysis.packedness`` / ``analysis.vmem`` — so the recursion rule
cannot drift between them.  ``pallas_call`` bodies are never descended
into: everything inside one is a single launch's VMEM-resident work,
not an HBM intermediate or a separate launch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr

# Higher-order call primitives whose operands map POSITIONALLY onto the
# inner jaxpr's invars — the only ones the dataflow passes flow values
# through.  Anything else with a nested jaxpr (scan, cond,
# reduce_window, custom_* with consts) is treated as an opaque eqn by
# the dataflow walk; the syntactic walk still descends so launch counts
# never under-report.
CALL_PRIMITIVES = frozenset({"jit", "closed_call", "core_call"})


def subjaxprs(param: Any) -> Iterator[Jaxpr]:
    """Yield every jaxpr nested inside one eqn param (lists included)."""
    if isinstance(param, ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, Jaxpr):
        yield param
    elif isinstance(param, (list, tuple)):
        for e in param:
            yield from subjaxprs(e)


def iter_eqns(jaxpr: Jaxpr) -> Iterator[Any]:
    """Yield every eqn in ``jaxpr``, recursing into nested jaxprs (jit /
    scan / cond bodies) but NOT into ``pallas_call`` kernel bodies — a
    kernel's internal eqns are one launch's VMEM work, not separate
    launches or HBM intermediates."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in subjaxprs(p):
                yield from iter_eqns(sub)


@dataclasses.dataclass(frozen=True)
class PallasLaunch:
    """One traced ``pallas_call``: the kernel's name and launch grid."""
    kernel: str
    grid: tuple[int, ...]


def kernel_name(eqn: Any) -> str:
    """The ``name=`` a ``pallas_call`` eqn was launched under, else the
    kernel function it was traced from."""
    src = eqn.params["jaxpr"].debug_info.func_src_info   # "<name> at <file>"
    return src.split(" at ", 1)[0]


def call_subjaxpr(eqn: Any) -> ClosedJaxpr | None:
    """The positionally-mapped inner jaxpr of a call primitive, or None.

    Only :data:`CALL_PRIMITIVES` qualify: their ``eqn.invars`` line up
    one-to-one with the inner jaxpr's invars, which is what lets the
    dataflow passes thread value identity through the call boundary.
    """
    if eqn.primitive.name not in CALL_PRIMITIVES:
        return None
    inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if isinstance(inner, Jaxpr):
        inner = ClosedJaxpr(inner, ())
    if isinstance(inner, ClosedJaxpr) and \
            len(inner.jaxpr.invars) == len(eqn.invars):
        return inner
    return None


def pallas_eqns(fn: Any, *args: Any) -> list[Any]:
    """Every traced ``pallas_call`` eqn of ``fn``, in trace order — the
    raw material for the launch inventory and the VMEM pass."""
    closed = jax.make_jaxpr(fn)(*args)
    return [eqn for eqn in iter_eqns(closed.jaxpr)
            if eqn.primitive.name == "pallas_call"]


def pallas_launches(fn: Any, *args: Any) -> list[PallasLaunch]:
    """Every pallas_call in ``fn``'s jaxpr, in trace order, with its
    kernel name and launch grid — the unit the analysis report's
    ``forward/`` cells record and regression-gate."""
    return [PallasLaunch(kernel=kernel_name(eqn),
                         grid=tuple(eqn.params["grid_mapping"].grid))
            for eqn in pallas_eqns(fn, *args)]


def pallas_grids(fn: Any, *args: Any) -> list[tuple[int, ...]]:
    """Launch grid of every pallas_call in ``fn``'s jaxpr, in trace order.

    The serving subsystem's GEMV-vs-GEMM evidence is launch-*shape*
    level: a batch ≤ 8 dense flush must lower to the N-major 1-D GEMV
    grid and a large flush to the 3-D (M, N, K) blocked GEMM grid
    (``kernels.ops.dispatch_batch``).
    """
    return [launch.grid for launch in pallas_launches(fn, *args)]


def count_pallas_calls(fn: Any, *args: Any) -> int:
    """Number of pallas_call primitives in ``fn``'s jaxpr — the
    kernel-launch count of the traced fn, recursing into jit bodies."""
    return len(pallas_launches(fn, *args))


def max_intermediate_bytes(fn: Any, *args: Any) -> tuple[int, tuple[int, ...]]:
    """(bytes, shape) of the largest intermediate any eqn produces —
    the HBM high-water evidence for the fused epilogues (an eqn output
    is an HBM-visible array at jaxpr level; pallas_call bodies are
    excluded, their internals live in VMEM)."""
    closed = jax.make_jaxpr(fn)(*args)
    best_bytes, best_shape = 0, ()
    for eqn in iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            aval = v.aval
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                nbytes = int(aval.size) * aval.dtype.itemsize
                if nbytes > best_bytes:
                    best_bytes, best_shape = nbytes, tuple(aval.shape)
    return best_bytes, best_shape
