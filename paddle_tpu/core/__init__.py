from . import dtype, enforce, flags, place, rng  # noqa: F401
from .dtype import (  # noqa: F401
    bfloat16,
    bool_,
    complex64,
    complex128,
    convert_dtype,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .place import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    get_device,
    get_place,
    set_device,
)
