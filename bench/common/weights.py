"""Seeded weights, made by the benchmark, never by the program.

A reference module (``references/<name>.py``) describes its architecture's
weights as a ``Spec``: global leaves, and the leaves of each kind of layer,
each with a shape, the dtype it is served in and an initializer. Every
leaf draws from its own key, folded from the seed, the layer index and the
leaf's name, so

  * ``program_params`` builds the whole tree on the device in one jitted
    call (layers of one run stacked, as the program's scan wants them);
  * ``layer_weights`` / ``global_weights`` rebuild one layer or the
    globals alone, with the same values, for the layer-by-layer reference.

Initializers: ``("normal", std)``, ``("norm", jitter)`` for 1 + jitter·N.
"""
from __future__ import annotations

import dataclasses
import zlib
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Leaf = Tuple[Tuple[int, ...], str, Tuple[Any, ...]]   # shape, dtype, init


@dataclasses.dataclass(frozen=True)
class Spec:
    globals_: Tuple[Tuple[str, Leaf], ...]
    kinds: Tuple[Tuple[str, Tuple[Tuple[str, Leaf], ...]], ...]
    runs: Tuple[Tuple[str, int], ...]        # (kind, layer count) in order

    @staticmethod
    def make(globals_: Dict[str, Leaf], kinds: Dict[str, Dict[str, Leaf]],
             runs: List[Tuple[str, int]]) -> "Spec":
        return Spec(tuple(globals_.items()),
                    tuple((k, tuple(v.items())) for k, v in kinds.items()),
                    tuple(runs))

    def kind_leaves(self, kind: str) -> Tuple[Tuple[str, Leaf], ...]:
        return dict(self.kinds)[kind]

    def layer_kinds(self) -> List[str]:
        return [k for k, n in self.runs for _ in range(n)]


def base_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, also one over 32 bits."""
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _draw(key, leaf: Leaf):
    shape, dtype, init = leaf
    kind = init[0]
    if kind == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * init[1]
    elif kind == "norm":
        x = 1.0 + jax.random.normal(key, shape, jnp.float32) * init[1]
    else:
        raise ValueError(f"unknown initializer {init!r}")
    return x.astype(dtype)


def _layer(key, layer_index, leaves):
    k = jax.random.fold_in(jax.random.fold_in(key, 1), layer_index)
    return {name: _draw(jax.random.fold_in(k, _name_id(name)), leaf)
            for name, leaf in leaves}


def _globals(key, leaves):
    k = jax.random.fold_in(key, 0)
    return {name: _draw(jax.random.fold_in(k, _name_id(name)), leaf)
            for name, leaf in leaves}


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{"attn/wq": x} → {"attn": {"wq": x}}."""
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        d = out
        *head, last = path.split("/")
        for p in head:
            d = d.setdefault(p, {})
        d[last] = v
    return out


@partial(jax.jit, static_argnums=0)
def _program_params(spec: Spec, key):
    groups = []
    first = 0
    for kind, count in spec.runs:
        leaves = spec.kind_leaves(kind)
        idx = jnp.arange(first, first + count)
        stacked = jax.vmap(lambda i: _layer(key, i, leaves))(idx)
        groups.append((nest(stacked),))
        first += count
    tree = nest(_globals(key, spec.globals_))
    tree["groups"] = tuple(groups)
    return tree


def program_params(spec: Spec, seed: int):
    """The whole tree in the program's layout: globals at the top,
    ``groups`` a tuple with one entry per run of layers, each a 1-tuple of
    a dict whose leaves are stacked over the run's layers."""
    return _program_params(spec, base_key(seed))


@partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(spec: Spec, kind: str, key, layer_index):
    return {n: v.astype(jnp.float32) for n, v in
            _layer(key, layer_index, spec.kind_leaves(kind)).items()}


def layer_weights(spec: Spec, seed: int, layer_index: int) -> Dict[str, Any]:
    """One layer's leaves (flat names), served values upcast to float32."""
    kind = spec.layer_kinds()[layer_index]
    return _layer_weights(spec, kind, base_key(seed), jnp.int32(layer_index))


@partial(jax.jit, static_argnums=0)
def _global_weights(spec: Spec, key):
    return {n: v.astype(jnp.float32)
            for n, v in _globals(key, spec.globals_).items()}


def global_weights(spec: Spec, seed: int) -> Dict[str, Any]:
    return _global_weights(spec, base_key(seed))


def param_bytes(spec: Spec) -> int:
    import numpy as np
    n = sum(int(np.prod(s)) * jnp.dtype(d).itemsize
            for _, (s, d, _) in spec.globals_)
    for kind, count in spec.runs:
        n += count * sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                         for _, (s, d, _) in spec.kind_leaves(kind))
    return n
