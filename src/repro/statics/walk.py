"""jaxpr IR walker — the traversal core every static check is built on.

A traced :class:`jax.extend.core.ClosedJaxpr` is a tree: equations at the top
level, with sub-jaxprs riding equation params (``scan``/``while`` carry
their bodies under ``jaxpr``, ``cond`` under ``branches``, ``pjit``/
``custom_jvp_call``/``custom_vjp_call`` under ``jaxpr``/``call_jaxpr``,
``pallas_call`` under ``jaxpr`` as well). The helpers here walk that tree
once and hand back flat views the checks consume:

* :func:`subjaxprs` / :func:`iter_eqns` — the raw traversal (drop-in for
  the per-test walkers that used to be copy-pasted across
  ``test_byz_trim_kernel.py`` / ``test_social_engine.py`` /
  ``test_hps_engine.py``).
* :func:`collect_avals` — every equation-output shape, the exact contract
  of the historical test helpers (outvars only; invars are some other
  equation's outvars or jaxpr inputs, so outputs cover every intermediate).
* :func:`collect_values` — the richer view: shape + dtype + producing
  primitive + path into the sub-jaxpr tree, for findings that need to say
  *where* a dense intermediate lives.
* :func:`collect_literals` — every scalar/small-array constant (equation
  ``Literal`` invars and the closed jaxpr's hoisted consts), for the
  subnormal-constant check.
* :func:`symbolize` — map concrete dims back to the symbolic sizes
  (``N``, ``E``, ``T``, ...) a fixture was built with, so findings read
  ``(N, N, m)`` instead of ``(64, 64, 3)``. Fixtures must keep symbol
  sizes pairwise distinct (the same discipline the historical tests used:
  "T = 37, distinct from N = 18 ... so the walker cannot confuse axes");
  ambiguous dim tables are rejected loudly rather than guessed at.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import numpy as np
from jax.extend import core as jcore

__all__ = [
    "Value",
    "subjaxprs",
    "iter_eqns",
    "collect_avals",
    "collect_values",
    "collect_literals",
    "symbolize",
    "trace",
]


def subjaxprs(val) -> Iterator[Any]:
    """Yield every jaxpr hiding inside one equation-param value.

    Handles ``ClosedJaxpr``, raw ``Jaxpr``, and (nested) lists/tuples of
    either — the shapes ``scan``/``cond``/``pjit``/``custom_*``/
    ``pallas_call`` store their bodies in.
    """
    if isinstance(val, jcore.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jcore.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from subjaxprs(item)


def iter_eqns(jaxpr, path: tuple = ()) -> Iterator[tuple[tuple, Any]]:
    """Depth-first ``(path, eqn)`` pairs over a jaxpr and every sub-jaxpr.

    ``path`` names the chain of enclosing primitives, e.g.
    ``("scan", "cond")`` for an equation inside a branch inside the scan
    body — what a finding prints so the offending value is locatable.
    """
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield path, eqn
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                yield from iter_eqns(sub, path + (eqn.primitive.name,))


def collect_avals(jaxpr, out: list) -> list:
    """Append every equation-output shape (recursing into sub-jaxprs).

    Signature and behavior are bit-for-bit the historical per-test walker:
    ``_collect_avals(jaxpr, [])`` on a raw ``Jaxpr`` returns the flat shape
    list the existing assertions consume.
    """
    for _, eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                out.append(v.aval.shape)
    return out


@dataclasses.dataclass(frozen=True)
class Value:
    """One intermediate value of a traced program."""

    shape: tuple[int, ...]
    dtype: Any
    prim: str            # producing primitive
    path: tuple[str, ...]  # enclosing primitives, outermost first

    @property
    def nbytes(self) -> int:
        size = int(np.prod(self.shape)) if self.shape else 1
        return size * _itemsize(self.dtype)

    def describe(self, dims: dict[str, int] | None = None) -> str:
        shape = symbolize(self.shape, dims) if dims else self.shape
        where = "/".join(self.path) or "<top>"
        return f"{self.prim} -> {shape} [{_dtype_name(self.dtype)}] at {where}"


def _itemsize(dtype) -> int:
    """Bytes per element, tolerating jax extended dtypes (``key<fry>`` is
    not a numpy dtype; a threefry key is two uint32 counters)."""
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return int(getattr(dtype, "itemsize", 8))


def _dtype_name(dtype) -> str:
    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def collect_values(jaxpr) -> list[Value]:
    """Every equation-output value with dtype/primitive/path metadata."""
    out: list[Value] = []
    for path, eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(Value(
                    shape=tuple(aval.shape),
                    dtype=getattr(aval, "dtype", np.float32),
                    prim=eqn.primitive.name,
                    path=path,
                ))
    return out


# Constants larger than this are data (likelihood tables, edge indices),
# not tuning literals; scanning every element of a big operand would make
# the subnormal check O(input size) for no added signal.
_LITERAL_SCAN_CAP = 64


def collect_literals(closed) -> list[tuple[tuple, Any]]:
    """``(path, value)`` for every compile-time constant small enough to be
    a hand-written literal: equation ``Literal`` invars (recursing into
    sub-jaxprs) plus the closed jaxpr's hoisted consts.
    """
    out: list[tuple[tuple, Any]] = []
    jaxpr = closed
    if isinstance(closed, jcore.ClosedJaxpr):
        for c in closed.consts:
            arr = np.asarray(c)
            if arr.size <= _LITERAL_SCAN_CAP:
                out.append(((), arr))
        jaxpr = closed.jaxpr
    for path, eqn in iter_eqns(jaxpr):
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                arr = np.asarray(v.val)
                if arr.size <= _LITERAL_SCAN_CAP:
                    out.append((path + (eqn.primitive.name,), arr))
    return out


def symbolize(shape: tuple[int, ...], dims: dict[str, int]) -> tuple:
    """Map a concrete shape back to fixture symbols: (64, 64, 3) with
    ``dims={"N": 64, "m": 3}`` reads ``("N", "N", "m")``.

    Dims whose size matches no symbol stay concrete ints. Two symbols
    sharing one size would make every report a guess, so ambiguous tables
    are rejected — pick pairwise-distinct fixture sizes instead (T=37
    against N=18 etc., the discipline the historical tests established).
    """
    rev: dict[int, str] = {}
    for name, size in dims.items():
        size = int(size)
        if size in rev:
            raise ValueError(
                f"ambiguous symbol table: {rev[size]!r} and {name!r} both "
                f"have size {size}; lint fixtures need pairwise-distinct dims"
            )
        rev[size] = name
    return tuple(rev.get(int(d), int(d)) for d in shape)


def trace(fn: Callable, *args, **kwargs) -> jcore.ClosedJaxpr:
    """``jax.make_jaxpr`` with kwargs threaded through — the one tracing
    entry every check shares (abstract evaluation only; nothing runs)."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
