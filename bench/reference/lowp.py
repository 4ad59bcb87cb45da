"""Number types that compute the reference's formulas in a lower
precision: every arithmetic result is rounded to the type's precision,
the way the hardware type would round it.  They make the control of the
`correct` comparison (the reference, in the program's place, one step
below the precision the configuration states)."""
from __future__ import annotations

import ml_dtypes
import numpy as np

ROUNDERS = {
    "float32": np.float32,
    "bfloat16": ml_dtypes.bfloat16,
}


def _val(x) -> float:
    return x.v if isinstance(x, _Low) else float(x)


class _Low:
    __slots__ = ("v",)
    rounder = None

    def __init__(self, v):
        self.v = float(self.rounder(_val(v)))

    def _new(self, x: float) -> "_Low":
        return type(self)(x)

    def __add__(self, o):
        return self._new(self.v + _val(o))

    __radd__ = __add__

    def __sub__(self, o):
        return self._new(self.v - _val(o))

    def __rsub__(self, o):
        return self._new(_val(o) - self.v)

    def __mul__(self, o):
        return self._new(self.v * _val(o))

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._new(self.v / _val(o))

    def __rtruediv__(self, o):
        return self._new(_val(o) / self.v)

    def __neg__(self):
        return self._new(-self.v)

    def __lt__(self, o):
        return self.v < _val(o)

    def __le__(self, o):
        return self.v <= _val(o)

    def __gt__(self, o):
        return self.v > _val(o)

    def __ge__(self, o):
        return self.v >= _val(o)

    def __eq__(self, o):
        return self.v == _val(o)

    __hash__ = None

    def __float__(self):
        return self.v

    def __repr__(self):
        return f"{type(self).__name__}({self.v!r})"


def number_type(precision: str):
    """-> a number type for `evaluator.evaluate_mapping(F=...)`:
    `float` for "float64", else a type rounding every result."""
    if precision == "float64":
        return float
    return type(f"Low_{precision}", (_Low,),
                {"rounder": ROUNDERS[precision], "__slots__": ()})
