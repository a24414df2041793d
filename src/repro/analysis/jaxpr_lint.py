"""Jaxpr-level dataflow passes: key-reuse taint, dead carries, dtype widening.

The passes run over the jaxprs of registered entry points (traced at abstract
bench-scale shapes by :mod:`repro.analysis.entrypoints`) and recurse through
every higher-order primitive (``pjit``, ``scan``, ``while``, ``cond``,
``custom_jvp/vjp``), so a bug inside a scan body four calls deep is attributed
to its source line via the equation's ``source_info``.

**KEY_REUSE taint.** PRNG keys are consumed by ``random_bits`` (sampling),
``random_split`` and ``random_fold_in`` (derivation). A safe program consumes
every key value exactly once; alias-forming ops (``random_wrap``/``unwrap``,
``convert_element_type``, ``reshape``, ``broadcast_in_dim``, ...) do not
launder identity, while ``split``/``fold_in`` *outputs* are fresh keys. Three
fire modes:

1. the same key value consumed >= 2 times within one jaxpr (the PR 1 bug:
   one key seeding both the batch draw and the J-tilde draw);
2. a scan carry key consumed in the body AND passed through unchanged — the
   next iteration consumes the identical key again;
3. a loop-invariant key (scan const / closed-over constant, or anything
   derived from only those through split/fold_in-with-invariant-data)
   sampled inside a scan body — the same draw every iteration.

Branches of ``cond`` are mutually exclusive, so per-operand consumption is
the max over branches, not the sum.

**DEAD_CARRY.** A scan carry position whose body invar is returned unchanged
and never read by any equation is dead state — copied through every
iteration of the fused chunk for nothing, and usually a forgotten update.
``jax.lax.scan`` hoists such a carry into a const before the jaxpr exists,
so a scan const the body never reads is flagged the same way.

**DTYPE_WIDEN.** Inside scan bodies only: an equation whose floating output
is strictly wider than every floating input silently multiplies the hot
loop's memory traffic.
"""
from __future__ import annotations

import inspect
import os
from collections import Counter, defaultdict
from typing import Any, Callable

import jax
import numpy as np
from jax.extend.core import Literal

from repro.analysis.findings import Finding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

# sampling actually derives bits; split/fold_in derive new keys — all three
# are one "consumption" of their key operand
SAMPLERS = ("random_bits",)
DERIVERS = ("random_split", "random_fold_in")
CONSUMERS = SAMPLERS + DERIVERS
# identity-preserving ops: the output IS the same key material
ALIAS_PRIMS = ("random_wrap", "random_unwrap", "convert_element_type",
               "copy", "reshape", "broadcast_in_dim", "transpose")


def _is_key_aval(aval) -> bool:
    """Typed PRNG keys, or the raw uint32[..., 2] threefry representation."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        if jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
            return True
    except (AttributeError, TypeError):
        pass
    shape = getattr(aval, "shape", ())
    return (np.dtype(dtype) == np.uint32 and len(shape) >= 1
            and shape[-1] == 2)


def _float_width(aval) -> int | None:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return None
    try:
        np_dtype = np.dtype(dtype)
    except TypeError:
        return None
    if jax.numpy.issubdtype(np_dtype, np.floating):
        return np_dtype.itemsize
    return None


def _source_of(eqn) -> tuple[str, int]:
    """(repo-relative path, line) of the user frame that emitted ``eqn``."""
    try:
        from jax._src import source_info_util as siu
        frame = siu.user_frame(eqn.source_info)
        if frame is None:
            return "", 0
        fname = frame.file_name
        line = getattr(frame, "start_line", None) or getattr(
            frame, "line_num", 0)
        if os.path.isabs(fname) and fname.startswith(ROOT):
            fname = os.path.relpath(fname, ROOT)
        return fname, int(line)
    except Exception:
        return "", 0


def _sub_jaxprs(eqn) -> list[Any]:
    """ClosedJaxprs whose invars map 1:1 onto ``eqn.invars`` (plain calls)."""
    out = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        if len(inner.invars) == len(eqn.invars):
            out.append(sub)
    return out


class _Analyzer:
    """One traversal context shared by all three passes."""

    def __init__(self, entry: str, fallback: tuple[str, int]):
        self.entry = entry
        self.fallback = fallback  # (path, line) when source_info is empty
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()

    # -- findings ----------------------------------------------------------

    def _emit(self, rule: str, message: str, eqn=None):
        if eqn is not None:
            path, line = _source_of(eqn)
        else:
            path, line = "", 0
        if not path:
            path, line = self.fallback
        f = Finding(rule=rule, path=path, line=line,
                    message=f"[{self.entry}] {message}")
        if f.fingerprint not in self._seen:
            self._seen.add(f.fingerprint)
            self.findings.append(f)

    # -- one jaxpr ---------------------------------------------------------

    def analyze(self, jaxpr, *, invariant_invars: frozenset[int],
                in_scan: bool) -> dict[int, int]:
        """Run all passes over ``jaxpr``; returns per-invar consumption counts.

        ``invariant_invars``: positions whose value cannot change across
        iterations of the nearest enclosing loop. ``in_scan``: whether this
        jaxpr executes inside some scan/while body (enables the
        loop-invariant-sampling and dtype-widening passes).
        """
        parent: dict[Any, Any] = {}

        def find(v):
            while v in parent:
                v = parent[v]
            return v

        counts: Counter = Counter()
        consumer_sites: dict[Any, list[tuple[str, Any]]] = defaultdict(list)
        used: set[Any] = set()
        invariant: set[Any] = set()
        for i, v in enumerate(jaxpr.invars):
            if i in invariant_invars:
                invariant.add(v)
        invariant.update(jaxpr.constvars)

        def is_invariant(v):
            return isinstance(v, Literal) or find(v) in {
                find(x) for x in invariant}

        def consume(v, eqn, how):
            if isinstance(v, Literal):
                return
            r = find(v)
            counts[r] += 1
            consumer_sites[r].append((how, eqn))
            if counts[r] == 2:
                sites = ", ".join(s for s, _ in consumer_sites[r])
                self._emit(
                    "KEY_REUSE",
                    f"key consumed {counts[r]}x without an interposed "
                    f"split/fold_in (consumers: {sites})", eqn)
            elif counts[r] > 2:
                pass  # already reported at the transition to 2

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            real_invars = [v for v in eqn.invars
                           if not isinstance(v, Literal)]
            used.update(real_invars)

            if prim in ALIAS_PRIMS and real_invars and eqn.outvars:
                # convert_element_type aliases key identity but is ALSO the
                # canonical float-widening op — check before aliasing through
                if in_scan and prim == "convert_element_type":
                    iw = _float_width(real_invars[0].aval)
                    ow = _float_width(eqn.outvars[0].aval)
                    if iw is not None and ow is not None and ow > iw:
                        self._emit(
                            "DTYPE_WIDEN",
                            f"{prim} widens float {iw * 8}-bit -> "
                            f"{ow * 8}-bit inside a scan body", eqn)
                parent[eqn.outvars[0]] = find(real_invars[0])
                if is_invariant(real_invars[0]):
                    invariant.add(eqn.outvars[0])
                continue

            if prim in CONSUMERS:
                key_v = eqn.invars[0]
                consume(key_v, eqn, prim)
                if prim in SAMPLERS and in_scan and is_invariant(key_v):
                    self._emit(
                        "KEY_REUSE",
                        "loop-invariant key sampled inside a scan body — "
                        "the same value is drawn every iteration", eqn)
                # split/fold_in outputs are fresh keys; fold_in with varying
                # data launders loop-invariance, with invariant data keeps it
                if prim in DERIVERS:
                    all_inv = all(is_invariant(v) for v in eqn.invars)
                    if all_inv:
                        invariant.update(eqn.outvars)
                continue

            if prim == "scan":
                self._scan(eqn, counts, find, consume)
                continue
            if prim == "while":
                self._while(eqn, counts, find, consume)
                continue
            if prim == "cond":
                self._cond(eqn, is_invariant, in_scan, consume)
                continue

            subs = _sub_jaxprs(eqn)
            if subs:
                for sub in subs:
                    inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                    inv = frozenset(
                        i for i, v in enumerate(eqn.invars)
                        if is_invariant(v))
                    sub_counts = self.analyze(inner, invariant_invars=inv,
                                              in_scan=in_scan)
                    for i, c in sub_counts.items():
                        for _ in range(c):
                            consume(eqn.invars[i], eqn, f"call:{prim}")
                continue

            # plain first-order primitive: dtype-widening check in scan
            if in_scan and eqn.outvars:
                in_widths = [w for v in eqn.invars
                             if (w := _float_width(v.aval)) is not None]
                if in_widths:
                    for ov in eqn.outvars:
                        ow = _float_width(ov.aval)
                        if ow is not None and ow > max(in_widths):
                            self._emit(
                                "DTYPE_WIDEN",
                                f"{prim} widens float "
                                f"{max(in_widths) * 8}-bit -> {ow * 8}-bit "
                                "inside a scan body", eqn)

            # invariance propagation through plain ops: output invariant iff
            # every input is
            if eqn.outvars and real_invars and all(
                    is_invariant(v) for v in eqn.invars):
                invariant.update(eqn.outvars)

        return {i: counts[find(v)] for i, v in enumerate(jaxpr.invars)
                if counts[find(v)]}

    # -- higher-order primitives ------------------------------------------

    def _scan(self, eqn, counts, find, consume):
        body = eqn.params["jaxpr"].jaxpr
        num_consts = eqn.params["num_consts"]
        num_carry = eqn.params["num_carry"]
        sub_counts = self.analyze(
            body, invariant_invars=frozenset(range(num_consts)),
            in_scan=True)
        body_used = self._used_invars(body)
        for i, c in sub_counts.items():
            for _ in range(c):
                consume(eqn.invars[i], eqn, "scan-body")
        for j in range(num_carry):
            in_v = body.invars[num_consts + j]
            out_v = body.outvars[j]
            if out_v is not in_v:
                continue
            pos = num_consts + j
            if sub_counts.get(pos, 0) >= 1 and _is_key_aval(in_v.aval):
                self._emit(
                    "KEY_REUSE",
                    f"scan carry {j} is a key that the body consumes AND "
                    "passes through unchanged — every iteration reuses the "
                    "identical key (split it and carry a fresh subkey)", eqn)
            elif in_v not in body_used:
                aval = in_v.aval
                self._emit(
                    "DEAD_CARRY",
                    f"scan carry {j} ({aval.dtype}{list(aval.shape)}) is "
                    "passed through unchanged and never read by the body",
                    eqn)
        # jax.lax.scan hoists a carry the body returns unchanged into a
        # const, so a dead carry reaches the jaxpr as a const nothing reads
        for i in range(num_consts):
            in_v = body.invars[i]
            if in_v not in body_used and in_v not in body.outvars:
                aval = in_v.aval
                self._emit(
                    "DEAD_CARRY",
                    f"scan input {i} ({aval.dtype}{list(aval.shape)}) is "
                    "never read by the body (a carry returned unchanged, "
                    "hoisted to a loop invariant)", eqn)

    def _while(self, eqn, counts, find, consume):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        c_counts = self.analyze(
            cond,
            invariant_invars=frozenset(range(cn)), in_scan=True)
        # body sees [body_consts, carry]; its consts sit at eqn.invars[cn:cn+bn]
        b_counts = self.analyze(
            body, invariant_invars=frozenset(range(bn)), in_scan=True)
        for i, c in c_counts.items():
            for _ in range(c):
                consume(eqn.invars[i], eqn, "while-cond")
        for i, c in b_counts.items():
            for _ in range(c):
                consume(eqn.invars[cn + i], eqn, "while-body")

    def _cond(self, eqn, is_invariant, in_scan, consume):
        branches = eqn.params["branches"]
        per_pos: Counter = Counter()
        inv = frozenset(i for i, v in enumerate(eqn.invars[1:])
                        if is_invariant(v))
        for br in branches:
            inner = br.jaxpr if hasattr(br, "jaxpr") else br
            sub = self.analyze(inner, invariant_invars=inv, in_scan=in_scan)
            for i, c in sub.items():
                per_pos[i] = max(per_pos[i], c)
        for i, c in per_pos.items():
            for _ in range(c):
                consume(eqn.invars[1 + i], eqn, "cond-branch")

    @staticmethod
    def _used_invars(jaxpr) -> set:
        used = set()
        for eqn in jaxpr.eqns:
            used.update(v for v in eqn.invars
                        if not isinstance(v, Literal))
        return used


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def lint_jaxpr(closed_jaxpr, *, entry: str = "<jaxpr>",
               fallback: tuple[str, int] = ("", 0)) -> list[Finding]:
    """Run all jaxpr passes over a ClosedJaxpr; returns findings."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    an = _Analyzer(entry, fallback)
    an.analyze(jaxpr, invariant_invars=frozenset(), in_scan=False)
    return an.findings


def lint_callable(fn: Callable, *args, entry: str | None = None,
                  **kwargs) -> list[Finding]:
    """Trace ``fn`` at the given (abstract or concrete) arguments and lint.

    Arguments may be ``jax.ShapeDtypeStruct`` trees — nothing executes on
    device; ``jax.make_jaxpr`` only abstract-evaluates.
    """
    if entry is None:
        entry = getattr(fn, "__name__", repr(fn))
    fallback = ("", 0)
    try:
        src = inspect.getsourcefile(fn)
        if src:
            if os.path.isabs(src) and src.startswith(ROOT):
                src = os.path.relpath(src, ROOT)
            fallback = (src, inspect.getsourcelines(fn)[1])
    except (OSError, TypeError):
        pass
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return lint_jaxpr(closed, entry=entry, fallback=fallback)
