"""Device places.

Parity with the reference's platform::Place hierarchy
(/root/reference/paddle/fluid/platform/place.h): CPUPlace, CUDAPlace,
CUDAPinnedPlace. TPU-native design: the primary place is TPUPlace (an XLA
device); CUDAPlace is accepted as a compat shim that maps onto the accelerator
so existing reference scripts run unmodified (BASELINE.json north star).
"""
from __future__ import annotations

import jax


class Place:
    """Base class for device placements."""

    _device_kind = None  # 'cpu' | 'accel'

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        if self._device_kind == 'cpu':
            devs = [d for d in jax.devices('cpu')] if _has_platform('cpu') else jax.devices()
        else:
            devs = jax.devices()  # default backend = accelerator when present
        return devs[self.device_id % len(devs)]


def _has_platform(name):
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


class CPUPlace(Place):
    _device_kind = 'cpu'

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """A single XLA accelerator device. The TPU-native analogue of CUDAPlace."""
    _device_kind = 'accel'


# The reference API names, mapped onto the accelerator so fluid scripts written
# for GPU run on TPU unmodified (see BASELINE.json north star).
class CUDAPlace(TPUPlace):
    pass


class XLAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(Place):
    """Host memory staging area. On TPU, maps to host RAM feeding the HBM DMA
    path used by the DataLoader (ref: paddle/fluid/memory/memcpy.cc)."""
    _device_kind = 'cpu'

    def __init__(self):
        super().__init__(0)


def on_tpu():
    """THE predicate for "this process computes on a TPU chip": kernel
    dispatch (ops/nn_ops.py, ops/pallas_conv.py), the partitioner's CPU jit
    shortcut, the default place, and the examples' and bench's full-size
    shapes all ask here, so no two call sites can disagree about what the
    chip is called."""
    return jax.default_backend() == 'tpu'


def is_compiled_with_cuda():
    """Compat: reports whether the accelerator backend is present."""
    return on_tpu()


def cuda_places(device_ids=None):
    """Compat shim for fluid.cuda_places(): one place per local accelerator."""
    n = len(jax.devices())
    ids = range(n) if device_ids is None else device_ids
    return [TPUPlace(i) for i in ids]


def cpu_places(device_count=1):
    return [CPUPlace() for _ in range(device_count)]


def tpu_places(device_ids=None):
    return cuda_places(device_ids)


def _get_paddle_place(place):
    """Normalize user-specified place (str | Place | None) to a Place."""
    if place is None:
        return TPUPlace(0) if is_compiled_with_cuda() else CPUPlace()
    if isinstance(place, Place):
        return place
    if isinstance(place, str):
        s = place.lower()
        if s == 'cpu':
            return CPUPlace()
        for prefix in ('tpu', 'gpu', 'cuda', 'xla'):
            if s.startswith(prefix):
                rest = s[len(prefix):].lstrip(':')
                return TPUPlace(int(rest) if rest else 0)
    raise ValueError(f"unknown place: {place!r}")


def cuda_pinned_places(device_count=None):
    """ref: fluid.cuda_pinned_places — pinned host staging areas; on TPU
    the DataLoader ring stages via device_put, so these are CPU places."""
    n = 1 if device_count is None else int(device_count)
    return [CUDAPinnedPlace() for _ in range(n)]
