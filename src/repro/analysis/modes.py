"""Access-mode checker: infer what a kernel *actually* reads and writes.

Every ``GrFunction`` declares per-argument access modes (``const`` /
``out`` / ``inout``) and the scheduler builds the dependency DAG from
nothing else.  The contract (paper §IV-D + the executor's install
convention) is:

* a kernel is a pure function of the device values of its arguments, in
  declared order, *including* output placeholders;
* it returns the new values of its writable (``out``/``inout``) arguments,
  in declared order — the executor installs them;
* ``const`` operands are never written, ``out`` operands' *prior values*
  are never read (their shape/dtype may be used — that is static).

The checker abstractly executes the kernel and compares behavior against
the declaration:

* **under-declaration** (correctness): the kernel returns more outputs
  than there are writable args (a computed value has no declared
  destination → the write drops DAG edges), a declared-``out`` operand's
  input *value* flows to an output (replay would read stale device
  contents), or the kernel mutates a ``const`` numpy operand in place;
* **over-declaration** (performance): the kernel returns fewer outputs
  than there are writable args (a declared write that never happens
  serializes every later reader), or a declared-``inout`` operand is never
  read (forces a spurious H2D prefetch/reload of dead data).

Inference is jaxpr-based: the kernel is traced with
:func:`jax.make_jaxpr` on shadow ``ShapeDtypeStruct`` operands and the
read-set is the backward reachability of the output variables through the
equations (recursing into sub-jaxprs, conservative where operand alignment
is unclear — conservatism can only *suppress* a report, never fabricate
one).  A concrete dual pass with read-only numpy operands catches in-place
mutation through ``const``.  Kernels that cannot be traced (``fn=None``
sim-only declarations, shape-sensitive kernels without
``lint_shapes`` hints) are reported as *skipped*, never as errors.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..core.element import AccessMode

try:  # pragma: no cover - exercised indirectly everywhere
    import jax
    from jax.extend import core as _jcore
except Exception:  # pragma: no cover - jax is a hard dep of the runtime
    jax = None
    _jcore = None


@dataclass(frozen=True)
class ModeIssue:
    """One mismatch between a declaration and observed kernel behavior."""

    function: str
    kind: str                   # "under" (correctness) | "over" (performance)
    message: str
    arg: Optional[int] = None   # argument position, when attributable
    declared: Optional[str] = None

    def __str__(self) -> str:
        where = f" arg {self.arg}" if self.arg is not None else ""
        return f"[{self.kind}] {self.function}{where}: {self.message}"


@dataclass
class ModeReport:
    """Result of analyzing one declared ``GrFunction``."""

    function: str
    modes: Tuple[str, ...]
    issues: List[ModeIssue] = field(default_factory=list)
    reads: Optional[Tuple[bool, ...]] = None   # inferred value-read per arg
    n_outputs: Optional[int] = None            # values the kernel returns
    skipped: Optional[str] = None              # reason when unanalyzable

    @property
    def ok(self) -> bool:
        return not self.issues

    def to_json(self) -> dict:
        return {
            "function": self.function,
            "modes": list(self.modes),
            "reads": list(self.reads) if self.reads is not None else None,
            "n_outputs": self.n_outputs,
            "skipped": self.skipped,
            "issues": [{"kind": i.kind, "arg": i.arg,
                        "declared": i.declared, "message": i.message}
                       for i in self.issues],
        }


# ----------------------------------------------------------------------
# jaxpr read-set inference
# ----------------------------------------------------------------------

def _is_literal(v: Any) -> bool:
    return _jcore is not None and isinstance(v, _jcore.Literal)


def _sub_jaxprs(eqn) -> List[Any]:
    """Collect inner (Closed)Jaxprs from an equation's params."""
    subs = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if _jcore is not None and isinstance(
                    item, (_jcore.Jaxpr, _jcore.ClosedJaxpr)):
                subs.append(item)
    return subs


def _inner_jaxpr(obj):
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _value_read_positions(jaxpr) -> set:
    """Positions of ``jaxpr.invars`` whose *value* can reach an output.

    Backward reachability from the outvars.  Call-like primitives with a
    single sub-jaxpr whose invars align 1:1 with the equation's invars
    (pjit, remat, custom_* wrappers) are recursed into so an operand that
    is dead *inside* the call does not count as read; anything whose
    operand alignment is unclear (scan/while/cond consts splitting) keeps
    every operand — conservative in the direction that only suppresses
    over-declaration reports.
    """
    live = {id(v) for v in jaxpr.outvars if not _is_literal(v)}
    for eqn in reversed(jaxpr.eqns):
        if not any(id(v) in live for v in eqn.outvars):
            continue
        used: Iterable[Any] = eqn.invars
        subs = _sub_jaxprs(eqn)
        if len(subs) == 1:
            inner = _inner_jaxpr(subs[0])
            if len(inner.invars) == len(eqn.invars):
                inner_reads = _value_read_positions(inner)
                used = [eqn.invars[i] for i in inner_reads]
        for v in used:
            if not _is_literal(v):
                live.add(id(v))
    return {i for i, v in enumerate(jaxpr.invars) if id(v) in live}


# ----------------------------------------------------------------------
# shadow operands
# ----------------------------------------------------------------------

_DEFAULT_SHAPE_CANDIDATES: Tuple[Tuple[Tuple[int, ...], Any], ...] = (
    ((8, 8), np.float32),
    ((8,), np.float32),
)


def _candidate_spec_sets(gf, n_args: int,
                         shapes: Optional[Sequence] = None):
    """Yield lists of (shape, dtype) pairs to trace with.

    Order of preference: explicit ``shapes`` argument, the declaration's
    ``lint_shapes`` hint, then generic fallbacks (all-2D f32, all-1D f32).
    """
    hint = shapes if shapes is not None else getattr(gf, "lint_shapes", None)
    if hint is not None:
        yield [(tuple(s), np.dtype(d)) for s, d in hint]
        return
    for shape, dtype in _DEFAULT_SHAPE_CANDIDATES:
        yield [(shape, np.dtype(dtype))] * n_args


def _concrete_fill(shape, dtype, salt: int) -> np.ndarray:
    n = int(np.prod(shape)) if shape else 1
    if np.issubdtype(dtype, np.integer):
        vals = (np.arange(n) + salt) % 7
    elif np.issubdtype(dtype, np.bool_):
        vals = (np.arange(n) + salt) % 2
    else:
        vals = (np.arange(n) + salt) * 0.125 + 0.5
    return np.asarray(vals, dtype=dtype).reshape(shape)


def _check_inplace_const(fn, specs, modes) -> Optional[int]:
    """Run the kernel on read-only numpy operands for every ``const`` arg;
    an in-place write through one raises ``ValueError: ... read-only``.
    Returns the offending arg position, or None."""
    arrs = []
    for i, (shape, dtype) in enumerate(specs):
        a = _concrete_fill(shape, dtype, salt=3 * i + 1)
        if not modes[i].writes:
            a.setflags(write=False)
        arrs.append(a)
    try:
        fn(*arrs)
    except ValueError as exc:
        msg = str(exc).lower()
        if "read-only" in msg or "not writeable" in msg:
            # Re-run flipping one const arg writable at a time to attribute.
            for i in range(len(arrs)):
                if modes[i].writes:
                    continue
                probe = [np.array(a) for a in arrs]
                for j in range(len(probe)):
                    if not modes[j].writes and j != i:
                        probe[j].setflags(write=False)
                try:
                    fn(*probe)
                except ValueError:
                    continue
                except Exception:
                    return None
                return i
            return -1  # some const arg, position unknown
    except Exception:
        pass        # concrete pass is best-effort; tracing is the oracle
    return None


# ----------------------------------------------------------------------
# the checker
# ----------------------------------------------------------------------

def analyze_function(gf, shapes: Optional[Sequence] = None) -> ModeReport:
    """Infer read/write behavior of one declared ``GrFunction`` and diff it
    against the declared access modes.  Never raises for unanalyzable
    kernels — those come back with ``report.skipped`` set."""
    modes: Tuple[AccessMode, ...] = tuple(gf.modes)
    mode_names = tuple(m.value for m in modes)
    name = getattr(gf, "name", None) or getattr(gf.fn, "__name__", "<fn>")
    report = ModeReport(function=name, modes=mode_names)
    fn = gf.fn
    if fn is None:
        report.skipped = "no kernel callable (sim-only declaration)"
        return report
    if jax is None:  # pragma: no cover - jax always present in this repo
        report.skipped = "jax unavailable"
        return report

    closed = None
    n_out = None
    last_error: Optional[str] = None
    chosen_specs = None
    for specs in _candidate_spec_sets(gf, len(modes), shapes):
        if len(specs) != len(modes):
            last_error = (f"lint_shapes has {len(specs)} entries for "
                          f"{len(modes)} declared args")
            continue
        sds = [jax.ShapeDtypeStruct(s, d) for s, d in specs]
        try:
            closed = jax.make_jaxpr(fn)(*sds)
            out_tree = jax.eval_shape(fn, *sds)
        except Exception as exc:
            last_error = f"{type(exc).__name__}: {exc}"
            continue
        n_out = (len(out_tree) if isinstance(out_tree, (tuple, list))
                 else 1)
        chosen_specs = specs
        break
    if closed is None:
        report.skipped = f"trace failed: {last_error}"
        return report

    read_positions = _value_read_positions(closed.jaxpr)
    report.reads = tuple(i in read_positions for i in range(len(modes)))
    report.n_outputs = n_out

    writable = [i for i, m in enumerate(modes) if m.writes]
    if n_out > len(writable):
        report.issues.append(ModeIssue(
            function=name, kind="under",
            message=(f"kernel returns {n_out} outputs but only "
                     f"{len(writable)} args are declared writable — a "
                     f"computed value has no declared destination, so its "
                     f"write carries no DAG edges (and the executor would "
                     f"reject the launch)")))
    elif n_out < len(writable):
        report.issues.append(ModeIssue(
            function=name, kind="over",
            message=(f"declares {len(writable)} writable (out/inout) args "
                     f"but the kernel returns {n_out} outputs — the phantom "
                     f"write serializes every later reader of that operand "
                     f"behind a store that never happens")))

    for i, m in enumerate(modes):
        is_read = i in read_positions
        if m is AccessMode.OUT and is_read:
            report.issues.append(ModeIssue(
                function=name, kind="under", arg=i, declared=m.value,
                message=("declared 'out' but the operand's input value "
                         "flows to an output — the runtime skips the H2D "
                         "refresh for pure outputs, so the kernel reads "
                         "stale device contents; declare 'inout'")))
        elif m is AccessMode.INOUT and not is_read:
            report.issues.append(ModeIssue(
                function=name, kind="over", arg=i, declared=m.value,
                message=("declared 'inout' but the operand's prior value "
                         "is never read — forces a spurious host→device "
                         "prefetch/reload of dead data; declare 'out'")))

    if chosen_specs is not None:
        bad = _check_inplace_const(fn, chosen_specs, modes)
        if bad is not None:
            report.issues.append(ModeIssue(
                function=name, kind="under",
                arg=bad if bad >= 0 else None, declared="const",
                message=("kernel mutates a 'const' operand in place — the "
                         "write is invisible to the DAG (no WAR/WAW edges) "
                         "and races every concurrent reader; declare "
                         "'inout'")))
    return report


def lint_functions(fns: Optional[Iterable] = None) -> List[ModeReport]:
    """Analyze every declared ``GrFunction`` (default: the process-wide
    declaration registry) and return one report per declaration."""
    if fns is None:
        from ..core.frontend import declared_functions
        fns = declared_functions()
    reports = []
    seen = set()
    for gf in fns:
        fid = getattr(gf, "fid", None)
        if fid is not None:
            if fid in seen:
                continue
            seen.add(fid)
        reports.append(analyze_function(gf))
    return reports
