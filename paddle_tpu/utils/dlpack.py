"""DLPack interop (reference: python/paddle/utils/dlpack.py)."""

from __future__ import annotations

from ..core.tensor import Tensor


def to_dlpack(x):
    arr = x._data if isinstance(x, Tensor) else x
    return arr.__dlpack__()


def from_dlpack(capsule):
    import jax
    arr = jax.dlpack.from_dlpack(capsule)
    return Tensor(arr)
