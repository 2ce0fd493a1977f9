"""Bytes of every numpy and jax array reachable from an object.

Walks attributes, dataclass fields, dicts, lists, tuples and sets. An
array that is a view counts its base's buffer, and each buffer counts
once, however many names reach it. Python objects that are not arrays
(dicts of ints, strings) are walked through but add nothing.
"""
from __future__ import annotations

import types

import numpy as np

_LEAVES = (str, bytes, int, float, bool, complex, type(None))
# code and classes are not data the object holds
_SKIP = (types.ModuleType, types.FunctionType, types.MethodType,
         types.BuiltinFunctionType, type)


def _jax_array_type():
    try:
        import jax
        return jax.Array
    except ImportError:     # the walker needs no jax to count numpy
        return ()


def array_bytes(root) -> int:
    jax_array = _jax_array_type()
    seen_obj: set[int] = set()
    seen_buf: set[int] = set()
    total = 0
    stack = [root]
    while stack:
        obj = stack.pop()
        if isinstance(obj, _LEAVES + _SKIP) or id(obj) in seen_obj:
            continue
        seen_obj.add(id(obj))
        if isinstance(obj, np.ndarray):
            base = obj
            while isinstance(base.base, np.ndarray):
                base = base.base
            if id(base) not in seen_buf:
                seen_buf.add(id(base))
                seen_obj.add(id(base))
                total += base.nbytes
            continue
        if jax_array and isinstance(obj, jax_array):
            total += int(obj.nbytes)
            continue
        if isinstance(obj, dict):
            stack.extend(obj.keys())
            stack.extend(obj.values())
        elif isinstance(obj, (list, tuple, set, frozenset)):
            stack.extend(obj)
        elif isinstance(obj, np.generic):
            continue
        else:
            d = getattr(obj, "__dict__", None)
            if d is not None:
                stack.extend(d.values())
            for name in getattr(type(obj), "__slots__", ()):
                if hasattr(obj, name):
                    stack.append(getattr(obj, name))
    return total
