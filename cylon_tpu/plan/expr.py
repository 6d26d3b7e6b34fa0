"""Fingerprintable column expressions for logical plans.

Table.select() takes an opaque Python lambda — fine for eager execution,
useless for a *plan*: a lambda cannot be fingerprinted (the durable
journal and the serve result cache key runs by content), compared for
CSE, or asked which columns it reads (column pruning needs the exact
read set).  This module is the lazy twin: a tiny expression tree
(``col``/``lit`` + arithmetic/comparison/logical operators) whose

- ``spec()`` is a canonical primitive tuple (feeds
  :func:`cylon_tpu.durable.run_fingerprint` unchanged) and names a
  *result*: it holds every literal's value,
- ``shape()`` names a *program*: operators, columns and literal types.
  The executor keys a stage program by it and hands the program the
  values of ``literals()`` as operands, by position, so a predicate with
  a date or a name nobody sent before compiles nothing.  A compared
  string goes as its packed words (``compute.string_scalar_words``; the
  shape holds how many).  A literal divisor stays in the shape with its
  value (zero is refused at trace time), and so does a string that is
  not compared,
- ``columns()`` is the exact read set (drives the optimizer's pruning),
- ``evaluate(env)`` lowers onto the SAME kernels the eager compute layer
  uses (``cylon_tpu.compute._col_math`` / ``_col_compare``), so a
  planned filter/derive is bit-identical to its eager counterpart.

Null semantics follow the compute layer: arithmetic propagates validity
conjunction (division additionally invalidates zero divisors), and a
filter keeps a row only when the predicate is True AND valid — the
pandas behavior (NaN comparisons are False).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Set, Tuple, Union

import numpy as np

from ..column import Column
from ..status import Code, CylonError

Scalar = Union[bool, int, float, str]

_CMP = ("eq", "ne", "lt", "gt", "le", "ge")
_MATH = ("add", "sub", "mul", "truediv")
_LOGICAL = ("and", "or")
_FLIP = {"lt": "gt", "gt": "lt", "le": "ge", "ge": "le",
         "eq": "eq", "ne": "ne"}


class Expr:
    """Base class: operator overloads build the tree."""

    # -- tree protocol --------------------------------------------------
    def spec(self) -> tuple:
        raise NotImplementedError

    def shape(self) -> tuple:
        raise NotImplementedError

    def literals(self) -> Tuple["Lit", ...]:
        """The literals whose values ``shape()`` leaves out, in its
        order: a stage program's scalar operands."""
        raise NotImplementedError

    def columns(self) -> Set[str]:
        raise NotImplementedError

    def evaluate(self, env: Dict[str, Column],
                 operands: Optional[Sequence] = None) -> Column:
        """``operands``, where given, stand in for the values of
        ``literals()``, one for one in its order: traced scalars inside a
        stage program.  By position, never by the ``Lit`` object: one
        object may stand in two places of a tree, and the next tree of
        the same shape has two values there."""
        raise NotImplementedError

    # -- operator surface ----------------------------------------------
    def _bin(self, op: str, other, flipped: bool = False) -> "Expr":
        other = _as_expr(other)
        left, right = (other, self) if flipped else (self, other)
        if isinstance(left, Lit) and isinstance(right, Lit):
            return _fold(op, left, right)  # constant-fold on the host
        return Bin(op, left, right)

    def __add__(self, o):
        return self._bin("add", o)

    def __radd__(self, o):
        return self._bin("add", o, flipped=True)

    def __sub__(self, o):
        return self._bin("sub", o)

    def __rsub__(self, o):
        return self._bin("sub", o, flipped=True)

    def __mul__(self, o):
        return self._bin("mul", o)

    def __rmul__(self, o):
        return self._bin("mul", o, flipped=True)

    def __truediv__(self, o):
        return self._bin("truediv", o)

    def __rtruediv__(self, o):
        return self._bin("truediv", o, flipped=True)

    def __eq__(self, o):  # type: ignore[override]
        return self._bin("eq", o)

    def __ne__(self, o):  # type: ignore[override]
        return self._bin("ne", o)

    def __lt__(self, o):
        return self._bin("lt", o)

    def __gt__(self, o):
        return self._bin("gt", o)

    def __le__(self, o):
        return self._bin("le", o)

    def __ge__(self, o):
        return self._bin("ge", o)

    def __and__(self, o):
        return self._bin("and", o)

    def __or__(self, o):
        return self._bin("or", o)

    def __invert__(self):
        return Not(self)

    def __neg__(self):
        return Neg(self)

    # == builds a comparison node, so identity must carry hashing
    __hash__ = object.__hash__

    def __bool__(self):
        raise CylonError(
            Code.Invalid,
            "a plan expression has no truth value; combine predicates "
            "with & / | / ~, not `and`/`or`/`not`")

    def __repr__(self) -> str:
        return f"Expr[{render(self)}]"


class Col(Expr):
    def __init__(self, name: str):
        self.name = str(name)

    def spec(self) -> tuple:
        return ("col", self.name)

    shape = spec

    def literals(self) -> Tuple["Lit", ...]:
        return ()

    def columns(self) -> Set[str]:
        return {self.name}

    def evaluate(self, env: Dict[str, Column], operands=None) -> Column:
        if self.name not in env:
            raise CylonError(Code.KeyError,
                             f"expression references unknown column "
                             f"{self.name!r} (have {sorted(env)})")
        return env[self.name]


class Lit(Expr):
    def __init__(self, value: Scalar):
        if not isinstance(value, (bool, int, float, str, np.generic)):
            raise CylonError(Code.Invalid,
                             f"literal must be a scalar, got {type(value)}")
        self.value = value.item() if isinstance(value, np.generic) else value

    def spec(self) -> tuple:
        return ("lit", type(self.value).__name__, self.value)

    def shape(self) -> tuple:
        if isinstance(self.value, str):
            return ("lit", "str", len(self.host_operand()))
        return ("lit", type(self.value).__name__)

    def literals(self) -> Tuple["Lit", ...]:
        return (self,)

    def host_operand(self):
        """What a stage hands its program for this literal: the value, or
        a string's packed words."""
        if isinstance(self.value, str):
            from ..compute import string_scalar_words

            return string_scalar_words(self.value)
        return self.value

    def operand(self, operands: Optional[Sequence]):
        """The value to compute with: the stage program's operand where
        it was given one, else the host value."""
        return operands[0] if operands else self.value

    def columns(self) -> Set[str]:
        return set()

    def evaluate(self, env: Dict[str, Column], operands=None) -> Column:
        # a bare literal never evaluates standalone: Bin special-cases
        # literal operands into the compute layer's scalar paths
        raise CylonError(Code.Invalid,
                         "a bare literal is not a column expression")


class Bin(Expr):
    def __init__(self, op: str, left: Expr, right: Expr):
        assert op in _CMP + _MATH + _LOGICAL, op
        self.op = op
        self.left = left
        self.right = right

    def spec(self) -> tuple:
        return ("bin", self.op, self.left.spec(), self.right.spec())

    def _in_key(self, side: Expr) -> bool:
        """A literal side whose value stays in the program's key and is
        no operand: a divisor, and a string that is not compared."""
        if not isinstance(side, Lit):
            return False
        if side is self.right and self.op == "truediv":
            return True
        return isinstance(side.value, str) and self.op not in _CMP

    def _side_literals(self, side: Expr) -> Tuple["Lit", ...]:
        return () if self._in_key(side) else side.literals()

    def shape(self) -> tuple:
        return ("bin", self.op) + tuple(
            side.spec() if self._in_key(side) else side.shape()
            for side in (self.left, self.right))

    def literals(self) -> Tuple["Lit", ...]:
        return (self._side_literals(self.left)
                + self._side_literals(self.right))

    def columns(self) -> Set[str]:
        return self.left.columns() | self.right.columns()

    def evaluate(self, env: Dict[str, Column], operands=None) -> Column:
        from .. import compute as compute_mod

        op = self.op
        lv, rv = self.left, self.right
        lops = rops = None  # each side's own operands, by position
        if operands is not None:
            n = len(self._side_literals(lv))
            lops, rops = operands[:n], operands[n:]
        if isinstance(lv, Lit) and isinstance(rv, Lit):
            raise CylonError(Code.Invalid,
                             "literal-only expression; fold it on the host")
        # scalar fast paths mirror the eager compute layer exactly
        if isinstance(rv, Lit):
            lc = lv.evaluate(env, lops)
            if op in _CMP:
                return compute_mod._col_compare(lc, rv.operand(rops), op,
                                                None)
            if op in _MATH:
                return compute_mod._col_math(lc, rv.operand(rops), op, None)
        if isinstance(lv, Lit):
            rc = rv.evaluate(env, rops)
            lo = lv.operand(lops)
            if op in _CMP:  # flip: lit < col  ==  col > lit
                return compute_mod._col_compare(rc, lo, _FLIP[op], None)
            if op in ("add", "mul"):
                return compute_mod._col_math(rc, lo, op, None)
            if op == "sub":  # lit - col == (-col) + lit
                return compute_mod._col_math(_neg_col(rc), lo, "add", None)
            if op == "truediv":  # lit / col: materialize the literal
                lc = _lit_column(lo, type(lv.value), rc)
                return compute_mod._col_math(lc, None, op, rc)
        if op in _LOGICAL and isinstance(rv, Lit):
            # a literal bool operand (often the residue of constant
            # folding, e.g. `pred & (lit(1) < lit(2))`): materialize it
            # against the evaluated side instead of crashing
            lc = lv.evaluate(env, lops)
            rc = _lit_column(_truth(rv.operand(rops)), bool, lc)
        elif op in _LOGICAL and isinstance(lv, Lit):
            rc = rv.evaluate(env, rops)
            lc = _lit_column(_truth(lv.operand(lops)), bool, rc)
        else:
            lc = lv.evaluate(env, lops)
            rc = rv.evaluate(env, rops)
        if op in _CMP:
            return compute_mod._col_compare(lc, None, op, rc)
        if op in _MATH:
            return compute_mod._col_math(lc, None, op, rc)
        # logical: both sides must be boolean columns
        import jax.numpy as jnp

        from .. import dtypes
        if lc.data.dtype != jnp.bool_ or rc.data.dtype != jnp.bool_:
            raise CylonError(Code.Invalid,
                             f"logical `{op}` needs boolean operands")
        data = (lc.data & rc.data) if op == "and" else (lc.data | rc.data)
        validity = lc.validity & rc.validity
        return compute_mod._result_col(data, validity, dtypes.bool_)


class Not(Expr):
    def __init__(self, e: Expr):
        self.e = e

    def spec(self) -> tuple:
        return ("not", self.e.spec())

    def shape(self) -> tuple:
        return ("not", self.e.shape())

    def literals(self) -> Tuple["Lit", ...]:
        return self.e.literals()

    def columns(self) -> Set[str]:
        return self.e.columns()

    def evaluate(self, env: Dict[str, Column], operands=None) -> Column:
        import jax.numpy as jnp

        from .. import compute as compute_mod
        from .. import dtypes

        c = self.e.evaluate(env, operands)
        if c.data.dtype != jnp.bool_:
            raise CylonError(Code.Invalid, "~ needs a boolean operand")
        return compute_mod._result_col(~c.data, c.validity, dtypes.bool_)


class Neg(Expr):
    def __init__(self, e: Expr):
        self.e = e

    def spec(self) -> tuple:
        return ("neg", self.e.spec())

    def shape(self) -> tuple:
        return ("neg", self.e.shape())

    def literals(self) -> Tuple["Lit", ...]:
        return self.e.literals()

    def columns(self) -> Set[str]:
        return self.e.columns()

    def evaluate(self, env: Dict[str, Column], operands=None) -> Column:
        return _neg_col(self.e.evaluate(env, operands))


def _neg_col(c: Column) -> Column:
    import jax.numpy as jnp

    from .. import dtypes

    if c.is_string or c.data.dtype == jnp.bool_:
        raise CylonError(Code.Invalid, "negation needs a numeric column")
    data = jnp.where(c.validity, -c.data, jnp.zeros((), c.data.dtype))
    return Column(data, c.validity, None, c.dtype)


def _truth(value):
    """A host literal's truth value; a traced operand casts on the device."""
    return bool(value) if isinstance(value, (bool, int, float, str)) else value


def _lit_column(value, kind: type, like: Column) -> Column:
    """Materialize a scalar (the host value, or a stage program's operand)
    of Python type ``kind`` as a full column with ``like``'s capacity —
    only for the rare non-flippable literal-first forms."""
    import jax.numpy as jnp

    from .. import dtypes

    if kind is str:
        raise CylonError(Code.Invalid, "string literals only compare")
    dt = (jnp.bool_ if kind is bool
          else jnp.int32 if kind is int else jnp.float32)
    cap = like.data.shape[0]
    data = jnp.full((cap,), value, dt)
    return Column(data, jnp.ones((cap,), bool), None,
                  dtypes.from_numpy_dtype(np.dtype(dt)))


def _fold(op: str, left: "Lit", right: "Lit") -> "Lit":
    """Host-side constant folding of literal-only subtrees (e.g.
    ``lit(1.0) - lit(0.1)`` inside a derive): a Bin over two literals
    could never evaluate against columns, so it folds at construction."""
    import operator as _op

    fns = {"add": _op.add, "sub": _op.sub, "mul": _op.mul,
           "truediv": _op.truediv, "eq": _op.eq, "ne": _op.ne,
           "lt": _op.lt, "gt": _op.gt, "le": _op.le, "ge": _op.ge,
           "and": lambda a, b: bool(a) and bool(b),
           "or": lambda a, b: bool(a) or bool(b)}
    try:
        return Lit(fns[op](left.value, right.value))
    except Exception as e:
        raise CylonError(Code.Invalid,
                         f"cannot fold literal expression "
                         f"({left.value!r} {op} {right.value!r}): {e}")


def host_operands(exprs: Sequence[Expr]) -> tuple:
    """What a stage hands its program: the literals of ``exprs``,
    expression after expression."""
    return tuple(node.host_operand() for e in exprs for node in e.literals())


def split_operands(exprs: Sequence[Expr], values: Sequence) -> list:
    """``values`` (the program's side of ``host_operands(exprs)``) as each
    expression's own ``evaluate`` operands."""
    out, at = [], 0
    for e in exprs:
        n = len(e.literals())
        out.append(tuple(values[at:at + n]))
        at += n
    return out


def _as_expr(v) -> Expr:
    if isinstance(v, Expr):
        return v
    return Lit(v)


def col(name: str) -> Col:
    """Reference a column by name."""
    return Col(name)


def lit(value: Scalar) -> Lit:
    """A scalar literal operand."""
    return Lit(value)


def render(e: Expr) -> str:
    """Human-readable one-line rendering (plan.explain)."""
    if isinstance(e, Col):
        return e.name
    if isinstance(e, Lit):
        return repr(e.value)
    if isinstance(e, Bin):
        sym = {"add": "+", "sub": "-", "mul": "*", "truediv": "/",
               "eq": "==", "ne": "!=", "lt": "<", "gt": ">", "le": "<=",
               "ge": ">=", "and": "&", "or": "|"}[e.op]
        return f"({render(e.left)} {sym} {render(e.right)})"
    if isinstance(e, Not):
        return f"~{render(e.e)}"
    if isinstance(e, Neg):
        return f"-{render(e.e)}"
    return repr(e)
