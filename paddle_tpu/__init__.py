"""paddle_tpu — a TPU-native deep learning framework.

A ground-up rebuild of the PaddlePaddle (Fluid ~2.0) capability surface on
JAX/XLA/Pallas/pjit.  Import as `import paddle_tpu as paddle` — the public API
mirrors python/paddle/__init__.py of the reference.

Architecture (see SURVEY.md §7):
  eager "dygraph"  = Tensor wrapper + jax.vjp autograd tape
  "static"/jit     = jax.jit over the same layer code via functional_call
  ParallelExecutor = pjit + sharding specs (paddle_tpu.distributed)
  fused ops        = Pallas kernels behind FLAGS_use_pallas_kernels
"""
from __future__ import annotations

import time as _time

_IMPORT_BEGAN = _time.perf_counter()    # start-up's scope `import` opens

from . import framework  # noqa: E402

# Persistent XLA compilation cache: compiled executables are reused across
# PROCESSES, so the second run of the same model skips XLA compilation.
# It lives where JAX_COMPILATION_CACHE_DIR says, else in FLAGS_jit_cache_dir
# (default <checkout>/.jax_cache; FLAGS_JIT_CACHE_DIR="" in the environment
# or paddle.set_flags({"FLAGS_jit_cache_dir": ""}) turns it off).
framework.flags.apply_jit_cache()

from .framework import (
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    bfloat16,
    bool_,
    complex64,
    complex128,
    device_count,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_flags,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
    uint8,
)
from .tensor import Tensor
from .creation import (
    arange,
    assign,
    bernoulli,
    clone,
    diag,
    diagflat,
    empty,
    empty_like,
    eye,
    full,
    full_like,
    linspace,
    logspace,
    meshgrid,
    multinomial,
    normal,
    ones,
    ones_like,
    rand,
    randint,
    randn,
    randperm,
    to_tensor,
    tril,
    triu,
    uniform,
    zeros,
    zeros_like,
)
from .tensor_ops import *  # noqa: F401,F403 — the paddle.tensor surface
from .tensor_ops import linalg  # noqa: F401
from .autograd import grad, is_grad_enabled, no_grad
from . import autograd  # noqa: F401

# subpackages (imported lazily-ish but exposed eagerly for API parity)
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import amp  # noqa: E402
from . import jit  # noqa: E402
from . import static  # noqa: E402
from . import distributed  # noqa: E402
from . import vision  # noqa: E402
from . import text  # noqa: E402
from . import hapi  # noqa: E402
from . import utils  # noqa: E402
from . import inference  # noqa: E402
from . import serving  # noqa: E402
from . import core  # noqa: E402
from . import distribution  # noqa: E402
from . import regularizer  # noqa: E402
from . import slim  # noqa: E402
from . import device  # noqa: E402
from . import onnx  # noqa: E402
from . import compat  # noqa: E402
from . import sysconfig  # noqa: E402
from . import reader  # noqa: E402
from . import incubate  # noqa: E402
from . import version  # noqa: E402
from .batch import batch  # noqa: E402 — reference python/paddle/__init__.py:27
from .hapi import Model  # noqa: E402
from .hapi import flops, summary  # noqa: E402
from .framework.io_state import load, save  # noqa: E402
from .nn.layer_base import ParamAttr  # noqa: E402
from .distributed.parallel import DataParallel  # noqa: E402

disable_static = lambda: None  # imperative is the default mode  # noqa: E731
enable_static = static.enable_static
in_dynamic_mode = lambda: not static.in_static_mode()  # noqa: E731
in_dygraph_mode = in_dynamic_mode  # fluid-era spelling (framework.py)

__version__ = "2.0.0+tpu"  # keep in sync with version.full_version


# -- fluid-era creation/compat surface (python/paddle/__init__.py aliases) --
def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """A trainable Tensor outside any Layer (fluid layer_helper-created
    parameter).  ParamAttr resolution (initializer/trainable/name) is the
    same as Layer.create_parameter (nn/layer_base.py:160): zeros for
    bias-like, Xavier-uniform otherwise, unless attr or
    default_initializer says otherwise."""
    from .framework.dtype import convert_dtype
    from .nn import initializer as _init
    from .nn.layer_base import ParamAttr, Parameter, _unique_name

    attr = ParamAttr._to_attr(attr)
    if attr is False:
        return None
    init = attr.initializer or default_initializer or (
        _init.Constant(0.0) if is_bias else _init.XavierUniform())
    value = init(tuple(int(d) for d in shape),
                 convert_dtype(dtype) or "float32")
    p = Parameter(value, name=name or attr.name or _unique_name("param"),
                  trainable=attr.trainable)
    p.optimize_attr["learning_rate"] = attr.learning_rate
    p.regularizer = attr.regularizer
    p.need_clip = attr.need_clip
    return p


def create_global_var(shape, value, dtype="float32", persistable=False,
                      force_cpu=False, name=None):
    """A non-trainable filled Tensor (fluid create_global_var)."""
    t = full(shape, value, dtype=dtype)
    t.stop_gradient = True
    if name:
        t.name = name
    return t


class LoDTensor(Tensor):
    """Compat shim: LoD (level-of-detail) tensors do not exist on TPU —
    variable-length batches are padded arrays + seq_len (COVERAGE.md,
    paddle_tpu.text.sequence).  Keeps the fluid construction patterns
    working — `LoDTensor()` + `.set(array, place)` and
    `LoDTensor(array)`; lod() is always empty."""

    def __init__(self, value=None, *args, **kwargs):
        import numpy as _np

        if value is None:
            value = _np.zeros((0,), _np.float32)
        super().__init__(value, *args, **kwargs)

    def set(self, array, place=None):
        import jax.numpy as _jnp

        self._value = _jnp.asarray(array)

    def lod(self):
        return []

    def recursive_sequence_lengths(self):
        return []

    def set_lod(self, lod):
        raise NotImplementedError(
            "LoD metadata is not representable on TPU; keep sequences "
            "padded with explicit seq_len (paddle_tpu.text.sequence)")


class LoDTensorArray(list):
    """Compat shim for the vector<LoDTensor> container (array ops live in
    paddle_tpu.static.nn TensorArray)."""


def get_cuda_rng_state():
    """RNG state for checkpoint round-trips.  There is no CUDA here: the
    framework RNG is a (seed, counter) chain (framework/random.py) and
    that pair is the state."""
    from .framework import random as _r

    return [("paddle_tpu", _r._state.seed_value, _r._state.counter)]


def set_cuda_rng_state(state):
    from .framework import random as _r

    if state and isinstance(state[0], tuple) and state[0][0] == "paddle_tpu":
        _, s, c = state[0]
        seed(int(s))
        _r._state.counter = int(c)
    else:
        raise ValueError("unrecognized rng state (expected the value from "
                         "paddle_tpu.get_cuda_rng_state())")


def fill_constant(shape, dtype, value, force_cpu=False, out=None, name=None):
    """fluid fill_constant alias of paddle.full (fill_constant_op.cc);
    out= fills the given variable in place (the fluid idiom discards the
    return value)."""
    t = full(shape, value, dtype=dtype)
    if out is not None:
        out.set_value(t)
        return out
    return t


# tensor-array ops at top level (python/paddle/tensor/__init__.py aliases)
from .static.nn import (  # noqa: E402,F401
    array_length, array_read, array_write, create_array)

# remaining reference top-level exports (python/paddle/__init__.py):
# callbacks module alias, device introspection, fluid-era tensor aliases
from .framework.place import (  # noqa: E402,F401
    get_cudnn_version, is_compiled_with_xpu)
from .hapi import callbacks  # noqa: E402,F401
reverse = flip  # noqa: F405 — fluid paddle.reverse (reverse_op.cc)
standard_normal = randn  # noqa: F405 — tensor/random.py alias

# fluid compat namespace LAST: fluid.layers re-exports the legacy
# aliases defined above (fill_constant etc.) at import time
from . import fluid  # noqa: E402,F401
from . import dataset  # noqa: E402,F401 — ref python/paddle/dataset/

# start-up's scope `import` closes: first to last line of this file
from .utils import profiler as _profiler  # noqa: E402

_profiler.startup().stamp("import", _IMPORT_BEGAN)
