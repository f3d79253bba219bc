"""Logical-axis sharding: named activation/parameter axes -> mesh axes.

Model code annotates tensors with *logical* axis names
(``constrain(h, ("batch", "seq", "embed"))``); the launcher activates a rule
set mapping logical names to physical mesh axes. Outside an active rule
context every annotation is a no-op, so tests and CPU smoke runs never touch
device placement.

Rule values may be ``None`` (replicated), a mesh-axis name, or a tuple of
mesh-axis names (e.g. batch -> ("pod", "data")).
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


@dataclass(frozen=True)
class Rules:
    """Logical-axis -> mesh-axis mapping."""
    mapping: Mapping[str, object]

    def spec(self, names: Sequence[str | None]) -> P:
        axes, used = [], set()
        for n in names:
            ax = self.mapping.get(n) if n is not None else None
            comps = (() if ax is None
                     else ((ax,) if isinstance(ax, str) else tuple(ax)))
            # a mesh axis may be consumed at most once per spec
            if comps and not (set(comps) & used):
                used.update(comps)
                axes.append(ax if isinstance(ax, str) else tuple(ax))
            else:
                axes.append(None)
        return P(*axes)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Rules):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = (mesh, rules)
    try:
        yield
    finally:
        _STATE.ctx = prev


def current_rules():
    return getattr(_STATE, "ctx", None)


def logical_sharding(names: Sequence[str | None]):
    """NamedSharding for the active context, or None."""
    ctx = current_rules()
    if ctx is None:
        return None
    mesh, rules = ctx
    return NamedSharding(mesh, rules.spec(names))


def constrain(x, names: Sequence[str | None]):
    """with_sharding_constraint under the active rules; identity otherwise."""
    ctx = current_rules()
    if ctx is None:
        return x
    mesh, rules = ctx
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(names)))
