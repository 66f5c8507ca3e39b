"""LoRA adapters (arXiv:2106.09685) on a frozen base model.

An adapted projection computes ``x @ W + (α/r) · (x @ A) @ B`` with the base
``W`` frozen and only ``A [n_in, r]`` and ``B [r, n_out]`` trained (``A ~
N(0, 1/n_in)`` and ``B = 0`` at the start, so the adapted model starts as
the base).  Adapters are a pytree of ``{"a", "b"}`` dicts placed where the
adapted weights sit in the base parameter tree; :func:`merge` puts each
beside its base weight as a :class:`LoraWeight`, which
:func:`repro.models.layers.mm` multiplies.  A plain array there multiplies
as before, so an unadapted model is unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["w", "a", "b"], meta_fields=["scale"])
@dataclasses.dataclass(frozen=True)
class LoraWeight:
    """A frozen weight ``w [..., n_in, n_out]`` with its adapter."""

    w: Any
    a: Any
    b: Any
    scale: float


def matmul(x, w: LoraWeight):
    return x @ w.w + ((x @ w.a) @ w.b) * w.scale


def _is_adapter(node) -> bool:
    return isinstance(node, dict) and set(node) == {"a", "b"}


def merge(base, adapters, scale: float):
    """``base`` with every weight that ``adapters`` names replaced by a
    :class:`LoraWeight`; the trees match wherever ``adapters`` has a node."""
    if adapters is None:
        return base
    if _is_adapter(adapters):
        return LoraWeight(base, adapters["a"], adapters["b"], scale)
    if isinstance(base, dict):
        out = dict(base)
        for k, v in adapters.items():
            out[k] = merge(base[k], v, scale)
        return out
    if isinstance(base, (list, tuple)):
        return type(base)(merge(b, a, scale) for b, a in zip(base, adapters))
    raise TypeError(f"no base weight for adapter node {type(adapters)}")
