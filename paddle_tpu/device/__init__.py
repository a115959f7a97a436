"""Device API.

TPU-native equivalent of reference ``paddle.device``
(python/paddle/device/__init__.py:284 set_device) and the Place hierarchy
(paddle/phi/common/place.h). Devices come from PjRt via ``jax.devices()``;
Places are thin named handles: ``tpu:0``, ``cpu``, ``gpu:0``.

There is no stream/event API to re-expose: XLA owns scheduling (async
dispatch + latency-hiding scheduler replace the reference's manual
calc/comm-stream model, reference paddle/phi/core/device_context.h).
``synchronize()`` maps to blocking on all live arrays.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Union

import jax

from ..core.backend import on_tpu

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace",
    "CUDAPinnedPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_cuda", "is_compiled_with_xpu", "is_compiled_with_rocm",
    "is_compiled_with_tpu", "synchronize", "get_default_backend",
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "max_memory_reserved", "memory_reserved",
]


class Place:
    """Named device handle (reference: phi::Place)."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and other.device_type == self.device_type
                and other.device_id == self.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    @property
    def jax_device(self):
        plat = _BACKEND_ALIASES.get(self.device_type, self.device_type)
        devs = [d for d in jax.devices() if d.platform == plat]
        if not devs:  # fall back to addressable non-cpu or cpu
            devs = jax.devices()
        return devs[min(self.device_id, len(devs) - 1)]

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_gpu_place(self):
        return self.device_type in ("gpu", "cuda")

    def is_tpu_place(self):
        return self.device_type == "tpu"


def CPUPlace():
    return Place("cpu")


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0):
    return Place("gpu", device_id)


def XPUPlace(device_id: int = 0):
    return Place("xpu", device_id)


def CUDAPinnedPlace():
    """reference: phi::CUDAPinnedPlace — page-locked host staging memory.
    Under PjRt, host staging is managed by the runtime; this is the
    host-memory Place handle."""
    return Place("cpu_pinned")


_BACKEND_ALIASES = {"gpu": "cuda", "tpu": "tpu"}

_current = threading.local()


def _accelerator_platform() -> Optional[str]:
    plats = {d.platform for d in jax.devices()}
    for p in ("tpu", "cuda", "rocm"):
        if p in plats:
            return p
    return None


def get_default_backend() -> str:
    p = _accelerator_platform()
    if p == "tpu":
        return "tpu"
    if p in ("cuda", "rocm"):
        return "gpu"
    return "cpu"


def set_device(device: Union[str, Place]) -> Place:
    """reference: python/paddle/device/__init__.py:284."""
    if isinstance(device, Place):
        place = device
    else:
        device = device.lower()
        if ":" in device:
            kind, idx = device.split(":")
            place = Place(kind, int(idx))
        else:
            place = Place(device, 0)
    _current.place = place
    try:
        jax.config.update("jax_default_device", place.jax_device)
    except Exception:
        pass
    return place


def get_device() -> str:
    place = getattr(_current, "place", None)
    if place is None:
        kind = get_default_backend()
        place = Place(kind, 0)
    if place.device_type == "cpu":
        return "cpu"
    return f"{place.device_type}:{place.device_id}"


def get_current_place() -> Place:
    place = getattr(_current, "place", None)
    if place is None:
        place = Place(get_default_backend(), 0)
    return place


def get_all_devices() -> List[str]:
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return jax.device_count()
    plat = _BACKEND_ALIASES.get(device_type, device_type)
    return len([d for d in jax.devices() if d.platform == plat])


def is_compiled_with_cuda() -> bool:
    return any(d.platform == "cuda" for d in jax.devices())


def is_compiled_with_rocm() -> bool:
    return any(d.platform == "rocm" for d in jax.devices())


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return on_tpu()


def synchronize(device=None):
    """Block until all pending XLA work completes (reference:
    paddle.device.synchronize / cudaDeviceSynchronize). XLA has no user
    streams; effectively a fence via a trivial blocking transfer."""
    import jax.numpy as jnp
    jax.block_until_ready(jnp.zeros(()))


def _place_of(value) -> Place:
    try:
        dev = list(value.devices())[0] if hasattr(value, "devices") else None
    except Exception:
        dev = None
    if dev is None:
        return Place("cpu")
    return Place(dev.platform, dev.id)


def _parse_to(tensor, *args, **kwargs):
    """Implements Tensor.to(device|dtype|tensor, ...)."""
    from ..core.tensor import Tensor
    from ..core.dtypes import convert_dtype
    device = kwargs.pop("device", None)
    dtype = kwargs.pop("dtype", None)
    kwargs.pop("blocking", None)
    for a in args:
        if isinstance(a, (str, Place)):
            try:
                dtype = convert_dtype(a) if isinstance(a, str) else dtype
                if dtype is not None and isinstance(a, str) and ":" not in a \
                        and a not in ("cpu", "gpu", "tpu", "xpu"):
                    continue
            except (ValueError, TypeError):
                pass
            device = a
        elif isinstance(a, Tensor):
            dtype = a.dtype
            device = a.place
        else:
            dtype = a
    value = tensor._value
    if device is not None:
        place = set_device.__wrapped__(device) if False else (
            device if isinstance(device, Place) else _str_to_place(device))
        value = jax.device_put(value, place.jax_device)
    if dtype is not None:
        value = value.astype(convert_dtype(dtype))
    out = Tensor(value, stop_gradient=tensor.stop_gradient)
    return out


def _str_to_place(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":")
        return Place(kind, int(idx))
    return Place(device, 0)


# ---------------------------------------------------------------------------
# Memory stats (reference: paddle/phi/core/memory/stats.h +
# paddle.device.cuda.max_memory_allocated — here backed by PjRt's
# per-device memory_stats())
# ---------------------------------------------------------------------------
def memory_stats(device=None) -> dict:
    """Raw PjRt allocator statistics for one device (bytes). Keys follow
    PjRt ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
    "bytes_limit", ...); returns {} when the backend exposes none."""
    d = _resolve(device)
    try:
        return dict(d.memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (reference:
    paddle.device.cuda.memory_allocated)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes allocated on the device (reference:
    paddle.device.cuda.max_memory_allocated)."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def max_memory_reserved(device=None) -> int:
    """Peak bytes reserved by the allocator pool; PjRt reports the
    reservation limit under bytes_limit/bytes_reserved."""
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved", s.get("bytes_reserved", 0)))


def memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_limit", 0)))


def _resolve(device):
    if device is None:
        return jax.local_devices()[0]
    if isinstance(device, Place):
        plat = {"gpu": "cuda"}.get(device.device_type, device.device_type)
        devs = [d for d in jax.local_devices() if d.platform == plat]
        return devs[device.device_id] if devs else jax.local_devices()[0]
    if isinstance(device, int):
        return jax.local_devices()[device]
    if isinstance(device, str):
        name, _, idx = device.partition(":")
        plat = {"gpu": "cuda"}.get(name, name)
        devs = [d for d in jax.local_devices() if d.platform == plat] \
            or jax.local_devices()
        return devs[int(idx) if idx else 0]
    return device


# -- Stream / Event (reference: python/paddle/device/__init__.py Stream,
# Event, current_stream, stream_guard; paddle/phi/core/device_context.h) --
#
# TPU-native semantics: XLA owns the hardware queues — every dispatch is
# async on ONE compute stream per device, and the latency-hiding scheduler
# replaces the reference's manual calc/comm stream split. This surface
# keeps the reference API contract (record/query/synchronize/wait
# ordering) with the XLA execution model underneath: a Stream is a named
# handle on a device's dispatch queue; an Event records a completion
# marker (a token array enqueued at record time) whose readiness tracks
# everything dispatched before it.
class Event:
    """reference: paddle.device.Event / cuda.Event."""

    def __init__(self, device=None, enable_timing: bool = False,
                 blocking: bool = False, interprocess: bool = False):
        self._device = _resolve_stream_device(device)
        self._arrays = None
        self._t_record = None
        self._t_done = None
        self.enable_timing = enable_timing

    def record(self, stream: "Stream" = None) -> None:
        """Mark a point behind all work dispatched so far: capture the
        arrays currently live on the device — their readiness implies
        every computation enqueued before this point has completed (a
        host-to-device token would ride the DMA path and NOT be ordered
        behind compute)."""
        import time as _time
        dev = stream._device if stream is not None else self._device
        self._arrays = [a for a in jax.live_arrays()
                        if dev in getattr(a, "devices", lambda: set())()]
        self._t_record = _time.perf_counter()
        self._t_done = None

    def query(self) -> bool:
        """True if all work recorded before the event has completed."""
        if self._arrays is None:
            return True
        live = [a for a in self._arrays if not a.is_deleted()]
        try:
            return all(bool(a.is_ready()) for a in live)
        except AttributeError:  # older jax: block (conservative)
            self.synchronize()
            return True

    def synchronize(self) -> None:
        import time as _time
        if self._arrays is not None:
            for a in self._arrays:
                if not a.is_deleted():
                    a.block_until_ready()
            if self._t_done is None:
                self._t_done = _time.perf_counter()

    def elapsed_time(self, end_event: "Event") -> float:
        """Milliseconds between two recorded+completed events. Host clock
        (XLA exposes no device timestamps): measured as completion-time
        delta when observed in order, falling back to the record-time
        delta if the end event was synchronized out of order."""
        if not (self.enable_timing and end_event.enable_timing):
            raise RuntimeError(
                "elapsed_time requires both events created with "
                "Event(enable_timing=True)")
        if self._arrays is None or end_event._arrays is None:
            raise RuntimeError(
                "elapsed_time: both events must be record()ed first")
        self.synchronize()
        end_event.synchronize()
        dt = end_event._t_done - self._t_done
        if dt <= 0.0:
            dt = max(end_event._t_record - self._t_record, 0.0)
        return dt * 1000.0


class Stream:
    """reference: paddle.device.Stream / cuda.Stream.

    XLA schedules one compute stream per device; extra Streams are
    ordering handles — work dispatched 'on' any stream of a device joins
    that device's queue, so wait_event/wait_stream reduce to event
    synchronization (the cross-stream overlap the reference manages by
    hand is done by XLA's latency-hiding scheduler instead)."""

    def __init__(self, device=None, priority: int = 2, blocking: bool =
                 False):
        self._device = _resolve_stream_device(device)
        self.priority = priority

    @property
    def device(self):
        return self._device

    def synchronize(self) -> None:
        """Block until everything dispatched on this device completes."""
        e = Event(self._device)
        e.record(self)
        e.synchronize()

    def record_event(self, event: Event = None) -> Event:
        event = event or Event(self._device)
        event.record(self)
        return event

    def wait_event(self, event: Event) -> None:
        """Order subsequent host dispatch after ``event`` (single XLA
        queue per device: completion wait gives the same ordering)."""
        event.synchronize()

    def wait_stream(self, stream: "Stream") -> None:
        stream.synchronize()
    # identity equality/hash (reference streams compare by handle):
    # distinct Stream objects are distinct ordering handles even on the
    # same device, and instances stay usable as dict/set keys


def _resolve_stream_device(device=None):
    """Stream/Event device resolution — the shared ``_resolve`` helper
    (platform-filtered, exact-index) accepting jax Devices verbatim."""
    return _resolve(device)


_CURRENT_STREAM: dict = {}


def current_stream(device=None) -> Stream:
    """reference: paddle.device.current_stream."""
    dev = _resolve_stream_device(device)
    key = _stream_key(dev)
    if key not in _CURRENT_STREAM:
        _CURRENT_STREAM[key] = Stream(dev)
    return _CURRENT_STREAM[key]


def _stream_key(dev):
    # jax device ids are only unique per backend — cpu:0 and tpu:0 both
    # have id 0, so the platform must be part of the key
    return (getattr(dev, "platform", "?"), getattr(dev, "id", 0))


def set_stream(stream: Stream) -> Stream:
    """reference: paddle.device.set_stream."""
    prev = current_stream(stream._device)
    _CURRENT_STREAM[_stream_key(stream._device)] = stream
    return prev


class stream_guard:
    """reference: paddle.device.stream_guard context manager."""

    def __init__(self, stream: Stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


__all__ += ["Stream", "Event", "current_stream", "set_stream",
            "stream_guard"]
