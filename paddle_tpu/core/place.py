"""Device / Place model.

TPU-native replacement for the reference's `Place` hierarchy
(reference: paddle/phi/common/place.h, python `paddle.set_device` in
python/paddle/device/__init__.py). A Place maps onto a jax.Device; there is no
driver-level device management here — PJRT owns that.
"""
import jax


class Place:
    """Base place. Compares by (kind, device_id)."""

    kind = "unknown"

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def get_device_id(self):
        return self.device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):
    """Accepted for API compatibility; maps to the default accelerator."""

    kind = "gpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    """Accepted for API compatibility; maps to the default accelerator."""


class NPUPlace(TPUPlace):
    """Accepted for API compatibility; maps to the default accelerator."""


class MLUPlace(TPUPlace):
    """Accepted for API compatibility; maps to the default accelerator."""


class IPUPlace(TPUPlace):
    """Accepted for API compatibility; maps to the default accelerator."""


class CustomPlace(Place):
    """Custom-device place (reference: phi::CustomPlace). Accepts a device
    type string; any PJRT-visible platform matches, else default backend."""

    def __init__(self, device_type, device_id=0):
        super().__init__(device_id)
        self.kind = str(device_type)


_current_place = None


def _default_place():
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "gpu":
        return CUDAPlace(0)
    return CPUPlace()


def set_device(device):
    """paddle.set_device — accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0'."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name == "cpu":
        _current_place = CPUPlace()
    elif name in ("tpu", "xpu", "npu"):
        _current_place = TPUPlace(idx)
    elif name in ("gpu", "cuda"):
        _current_place = CUDAPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    return _current_place


def get_device():
    p = get_place()
    return f"{p.kind}:{p.device_id}"


def get_place():
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def is_compiled_with_cinn():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_ipu():
    return False


def get_cudnn_version():
    return None


def device_count():
    return len(jax.devices())
