"""Scope: the runtime name -> value store.

reference: paddle/fluid/framework/scope.h:38 (hierarchical Scope) and
variable.h (type-erased Variable). Here values are jax Arrays (device
buffers), host ``LoDTensor``s, numpy arrays, or arbitrary host objects (RAW).
Hierarchy is kept for control-flow/step scopes and the ``global_scope()``
singleton matches executor.py's.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


class Scope(object):
    def __init__(self, parent: "Scope" = None):
        self.parent = parent
        self._vars: Dict[str, Any] = {}
        self._kids = []
        # bumped when the VARIABLE SET changes (new name added/removed) —
        # executors key their state-signature memo on it; value updates
        # don't bump (shapes/dtypes of existing entries are re-validated
        # only when the set changes, which is when new persistables appear)
        self._names_version = 0
        # bumped by every write that changes what the scope holds (``var``'s
        # create, ``set_var`` of another object, ``erase``): an executor
        # that holds a step's new state back (``Executor.run(hold=True)``)
        # may commit it only if the scope it was computed from was not
        # written since
        self._writes = 0

    def var(self, name: str):
        """Find-or-create (reference: Scope::Var)."""
        if name not in self._vars:
            self._vars[name] = None
            self._names_version += 1
            self._writes += 1
        return self._vars[name]

    def find_var(self, name: str):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def set_var(self, name: str, value):
        # write-through to the scope that owns the name, else local
        s = self
        while s is not None:
            if name in s._vars:
                if s._vars[name] is not value:
                    # a save program's write-back of the very object the
                    # scope holds changes nothing
                    s._vars[name] = value
                    s._writes += 1
                return
            s = s.parent
        self._vars[name] = value
        self._names_version += 1
        self._writes += 1

    def erase(self, name: str):
        if name in self._vars:
            self._names_version += 1
            self._writes += 1
        self._vars.pop(name, None)

    def write_stamp(self):
        """The write counts of this scope and its parents: equal stamps
        mean that no variable a lookup from here can reach was written in
        between."""
        stamp, s = [], self
        while s is not None:
            stamp.append(s._writes)
            s = s.parent
        return tuple(stamp)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []

    def local_var_names(self):
        return list(self._vars)

    def __contains__(self, name):
        return self.has_var(name)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return _guard()
