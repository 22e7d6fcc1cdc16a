import os as _os

import jax as _jax


def _x64_default() -> bool:
    """x64 policy (ref framework.proto VarType lists FP64/INT64 as
    first-class dtypes, so CPU keeps them for API parity).

    TPU compiles reject f64 outright, so on accelerator backends x64 stays
    OFF: JAX then canonicalizes any f64 leak (np.float64 scalars such as
    ``x / np.sqrt(d)``, numpy-initialized weights) to f32 at trace time
    instead of producing a fatal ``(f64) -> f32`` convert in Mosaic/XLA.
    This is a policy, not a per-callsite patch: no user script can poison a
    TPU compile with f64 constants. Override with PADDLE_TPU_ENABLE_X64=0/1.
    """
    env = _os.environ.get("PADDLE_TPU_ENABLE_X64")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "off", "")
    if _os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return True
    # Decide from configuration WITHOUT initializing the XLA backend: a
    # default_backend() probe here would lock in local devices and break a
    # later jax.distributed.initialize() (multi-host fleets init lazily —
    # see distributed/parallel.py / role_maker.py).
    cfg = getattr(_jax.config, "jax_platforms", None) or ""
    plats = {p.strip().lower() for p in cfg.split(",") if p.strip()}
    if plats:
        return plats <= {"cpu"}
    # Unknown target: stay 32-bit — f64 canonicalization is harmless on
    # CPU but f64 leakage is fatal on TPU.
    return False


_jax.config.update("jax_enable_x64", _x64_default())

if not _jax.config.jax_enable_x64:
    # 64-bit dtype requests canonicalize to 32-bit on accelerators; the
    # per-callsite truncation warning would otherwise fire on every astype.
    import warnings as _warnings

    _warnings.filterwarnings(
        "ignore", message="Explicitly requested dtype.*is not available")


def enable_x64(flag: bool = True) -> None:
    """Runtime override of the 64-bit policy (affects subsequent traces)."""
    _jax.config.update("jax_enable_x64", bool(flag))

from . import dtype as dtypes
from .dtype import (
    bfloat16,
    bool_,
    complex64,
    complex128,
    convert_dtype,
    dtype_name,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    is_floating,
    is_integer,
    set_default_dtype,
    uint8,
)
from .errors import (
    EnforceError,
    InvalidArgumentError,
    NotFoundError,
    OutOfRangeError,
    UnimplementedError,
    enforce,
    enforce_eq,
)
from .flags import define_flag, flag, get_flags, set_flags
from .place import (
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    XPUPlace,
    device_count,
    get_device,
    get_place,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    set_device,
)
from .random import get_seed, in_rng_guard, rng_guard, seed, split_key
