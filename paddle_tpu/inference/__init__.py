"""paddle.inference — the serving path.

Reference parity: paddle/fluid/inference/ (SURVEY.md §2.6):
  * AnalysisConfig        → Config (api/analysis_config.cc knob surface;
                            CUDA/MKLDNN/TensorRT knobs accepted and inert)
  * AnalysisPredictor     → Predictor (api/analysis_predictor.cc:306 Run /
                            ZeroCopyRun) — named input/output handles
  * save/load_inference_model (fluid io.py:1198/1411) — export artifact
TPU-native: the "optimized program" is an AOT-compiled function.  Export
serializes the jitted forward as StableHLO via jax.export (.pdexport) plus
weights (.pdiparams) and an input-spec manifest (.pdmodel.json); the
predictor deserializes and calls it — no Python model code needed at serve
time (the AnalysisPredictor contract).  A pickle fallback (.pdmodel) keeps
models with python-side control flow loadable.
"""
from __future__ import annotations

import json
import logging
import os
import pickle

import numpy as np

import jax
import jax.export  # noqa: F401 - a submodule jax does not import by itself
import jax.numpy as jnp

from ..framework.dtype import convert_dtype
from ..tensor import Tensor
from ..utils import profiler as _profiler

logger = logging.getLogger("paddle_tpu.inference")

__all__ = ["Config", "Predictor", "create_predictor",
           "save_inference_model", "load_inference_model", "PrecisionType",
           "DataType", "PlaceType", "aot_compile", "spec_tree"]


class PrecisionType:
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


class DataType:
    """Tensor element types over the serving boundary
    (paddle_infer_declare.h PaddleDType)."""
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6


class PlaceType:
    """Handle placement (paddle_tensor.h PlaceType); TPU serves from the
    accelerator, kCPU is the host fallback."""
    kUNK = -1
    kCPU = 0
    kGPU = 1
    kTPU = 2
    kXPU = 3


def _natural_key(name):
    """Sort key splitting digit runs so x2 < x10 (AnalysisPredictor binds
    feeds by declaration order; numeric-suffix names must follow it)."""
    import re
    return [int(p) if p.isdigit() else p
            for p in re.split(r"(\d+)", str(name))]


# Inert-knob warnings fire ONCE per process per knob (serving loops call
# these from config templates; per-call spam would drown real logs).
_warned_inert: set[str] = set()


def _warn_inert(knob: str, detail: str):
    if knob not in _warned_inert:
        _warned_inert.add(knob)
        logger.warning(
            "inference.Config.%s is accepted but INERT on this backend — "
            "%s (XLA is the engine; see MIGRATION.md §4)", knob, detail)


class Config:
    """AnalysisConfig parity (api/analysis_config.cc)."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        # paddle 2.x: Config(path_prefix) or Config(model_file, params_file)
        if model_dir is not None and prog_file is None:
            self._path_prefix = str(model_dir)
        elif prog_file is not None:
            self._path_prefix = os.path.splitext(str(model_dir))[0]
        else:
            self._path_prefix = None
        self._use_tpu = True
        self._precision = PrecisionType.Float32
        self._switches = {}

    def set_model(self, model_dir, params_file=None):
        self._path_prefix = os.path.splitext(str(model_dir))[0]

    def model_dir(self):
        return self._path_prefix

    # device knobs — TPU is the target; CUDA knobs accepted, inert (and
    # say so once, so serving users aren't misled into thinking a GPU /
    # TensorRT / MKLDNN path is active)
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        _warn_inert("enable_use_gpu", "no CUDA path exists; the model "
                    "serves from the TPU/CPU XLA backend")
        self._switches["use_gpu"] = True

    def disable_gpu(self):
        self._switches["use_gpu"] = False

    def enable_xpu(self, *a, **k):
        _warn_inert("enable_xpu", "no XPU path exists")
        self._switches["use_xpu"] = True

    def enable_tpu(self):
        self._use_tpu = True
        self._switches["use_tpu"] = True

    def use_tpu(self) -> bool:
        return self._use_tpu

    def set_cpu_math_library_num_threads(self, n):
        self._switches["cpu_threads"] = n

    def enable_mkldnn(self):
        _warn_inert("enable_mkldnn", "MKLDNN is a documented non-goal")
        self._switches["mkldnn"] = True

    def enable_tensorrt_engine(self, *a, **k):
        _warn_inert("enable_tensorrt_engine",
                    "TensorRT is a documented non-goal")
        self._switches["tensorrt"] = True  # inert: XLA is the engine

    def enable_memory_optim(self):
        self._switches["memory_optim"] = True

    def switch_ir_optim(self, x=True):
        self._switches["ir_optim"] = x

    def switch_use_feed_fetch_ops(self, x=False):
        self._switches["feed_fetch_ops"] = x

    def switch_specify_input_names(self, x=True):
        self._switches["specify_input_names"] = x

    def set_precision(self, p):
        self._precision = p

    def summary(self):
        return json.dumps({"path": self._path_prefix,
                           "switches": self._switches}, indent=2)


_MISSING = object()  # bucket-cache sentinel: None = "compile failed, use
                     # per-call dispatch" is itself a cached outcome


class _Handle:
    """ZeroCopy input/output handle (api/details/zero_copy_tensor.cc)."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def reshape(self, shape):
        pass  # shapes come from the bound array

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._value)

    def share_external_data(self, arr):
        self._value = arr


class Predictor:
    """AnalysisPredictor parity: named handles + Run loop.

    Serving addition: a bucket-aware callable cache — every distinct
    input-shape signature ("bucket") is AOT-lowered and compiled ONCE
    (`warm()` does it ahead of traffic), and subsequent `run` calls on
    that bucket go straight to the compiled executable with zero
    retracing/recompilation.  `compile_count` exposes the number of
    bucket compiles so serving tests can tripwire recompile storms.
    """

    def __init__(self, config: Config):
        if isinstance(config, str):
            config = Config(config)
        self.config = config
        self._bucket_cache = {}
        self.compile_count = 0
        prefix = config.model_dir()
        if prefix is None:
            raise ValueError("Config has no model path")
        self._load(prefix)

    @classmethod
    def from_layer(cls, layer):
        """Serve an in-memory Layer through the same Predictor surface
        (bucket cache included) without an export round-trip."""
        self = cls.__new__(cls)
        self.config = None
        self._bucket_cache = {}
        self.compile_count = 0
        self._input_specs = None
        self._init_from_layer(layer)
        return self

    # -- loading ----------------------------------------------------------
    def _load(self, prefix):
        manifest_path = prefix + ".pdmodel.json"
        export_path = prefix + ".pdexport"
        if os.path.exists(manifest_path) and os.path.exists(export_path):
            with open(manifest_path) as f:
                manifest = json.load(f)
            with open(export_path, "rb") as f:
                self._exported = jax.export.deserialize(f.read())
            self._input_names = manifest["input_names"]
            self._output_names = manifest["output_names"]
            self._input_specs = manifest.get("input_specs")
            params = {}
            aot_params = prefix + ".pdaotparams"
            with open(aot_params if os.path.exists(aot_params)
                      else prefix + ".pdiparams", "rb") as f:
                raw = pickle.load(f)
            for k, v in raw.items():
                params[k] = jnp.asarray(v)
            self._params = params
            self._mode = "aot"
            return
        # fallback: pickled Layer artifact (paddle_tpu.jit.save format)
        from .. import jit as _jit
        self._input_specs = None
        self._init_from_layer(_jit.load(prefix))

    def _init_from_layer(self, layer):
        layer.eval()
        from ..nn.layer_base import functional_call, state_pytrees
        params, buffers = state_pytrees(layer)

        def fwd(params, *args):
            out, _ = functional_call(layer, params,
                                     tuple(Tensor(a) for a in args),
                                     buffers=buffers)
            if isinstance(out, (tuple, list)):
                return tuple(o.value for o in out)
            return (out.value,)

        self._params = params
        self._jitted = jax.jit(fwd)
        self._input_names = None  # discovered at first run
        self._output_names = None
        self._mode = "jit"

    # -- bucket-aware callable cache --------------------------------------
    @staticmethod
    def _bucket_key(arrays):
        return tuple((tuple(int(d) for d in a.shape),
                      str(np.dtype(a.dtype))) for a in arrays)

    def _get_bucket(self, arrays):
        """Compiled executable for this exact input signature (compiling
        it on first sight), or None when AOT lowering is unavailable for
        it — callers then take the legacy dispatch path."""
        key = self._bucket_key(arrays)
        fn = self._bucket_cache.get(key, _MISSING)
        if fn is not _MISSING:
            return fn
        try:
            specs = [jax.ShapeDtypeStruct(shape, np.dtype(dt))
                     for shape, dt in key]
            if self._mode == "aot":
                exported = self._exported

                def call(params, *xs):
                    return exported.call(*jax.tree.leaves(params), *xs)

                jitted = jax.jit(call)
            else:
                jitted = self._jitted
            # start-up's row of this bucket, named by its inputs' shapes
            boot = _profiler.startup()
            with boot.executable(boot.under("build/predict." + "_".join(
                    "x".join(map(str, shape)) for shape, _ in key))):
                with boot.scope(boot.under("lower")):
                    lowered = jitted.lower(self._params, *specs)
                with boot.scope(boot.under("compile")):
                    fn = lowered.compile()
            self.compile_count += 1
        except Exception as e:  # noqa: BLE001 - bucket cache is an optimization
            logger.debug("bucket compile failed for %s (%s: %s) — using "
                         "per-call dispatch", key, type(e).__name__, e)
            fn = None
        self._bucket_cache[key] = fn
        return fn

    def warm(self, shapes, dtypes=None):
        """AOT-compile the bucket for `shapes` (one shape tuple per
        input, batch dim included) ahead of traffic.  Returns True when
        the bucket is servable without further compilation."""
        if dtypes is None:
            dtypes = [s["dtype"] for s in (self._input_specs or [])] \
                or ["float32"] * len(shapes)
        arrays = [np.zeros(tuple(shape), np.dtype(dt))
                  for shape, dt in zip(shapes, dtypes)]
        fn = self._get_bucket(arrays)
        if fn is not None and self._mode == "jit" \
                and self._input_names is None:
            self.run(arrays)  # discover input/output names once
        return fn is not None

    # -- handle API (reference get_input_handle/get_output_handle) --------
    def get_input_names(self):
        return list(self._input_names or [])

    def get_output_names(self):
        return list(self._output_names or [])

    def get_input_handle(self, name):
        if not hasattr(self, "_in_handles"):
            self._in_handles = {}
        return self._in_handles.setdefault(name, _Handle(name))

    def get_output_handle(self, name):
        if not hasattr(self, "_out_handles"):
            self._out_handles = {}
        return self._out_handles.setdefault(name, _Handle(name))

    def run(self, inputs=None):
        """Run with positional numpy inputs (returns list of numpy), or
        with bound handles when inputs is None (ZeroCopyRun path).

        Dispatch goes through the bucket cache: the first call on a new
        input signature AOT-compiles it, every later call reuses the
        compiled executable (zero retrace/recompile — the property the
        serving engine's warmup relies on)."""
        if inputs is None:
            # Natural-sort fallback: lexicographic sorted() would bind x10
            # before x2 for models with 11+ inputs (advisor r1/r2 finding).
            names = self._input_names or sorted(
                getattr(self, "_in_handles", {}), key=_natural_key)
            inputs = [self._in_handles[n]._value for n in names]
        arrays = [np.asarray(x.numpy() if isinstance(x, Tensor) else x)
                  for x in inputs]
        fn = self._get_bucket(arrays)
        if fn is not None:
            outs = fn(self._params, *arrays)
        elif self._mode == "aot":
            outs = self._exported.call(*jax.tree.leaves(self._params),
                                       *(jnp.asarray(a) for a in arrays))
        else:
            outs = self._jitted(self._params, *arrays)
        if self._input_names is None:
            self._input_names = [f"x{i}" for i in range(len(arrays))]
            self._output_names = [f"out{i}" for i in range(
                len(outs) if isinstance(outs, (tuple, list)) else 1)]
        outs = [np.asarray(o) for o in (outs if isinstance(outs, (tuple, list))
                                        else [outs])]
        for i, n in enumerate(self._output_names or []):
            if hasattr(self, "_out_handles") and n in self._out_handles:
                self._out_handles[n]._value = outs[i]
        return outs


def create_predictor(config):
    return Predictor(config)


def spec_tree(tree):
    """ShapeDtypeStructs mirroring an argument pytree — the AOT lowering
    input for ``aot_compile``.  Scalars should already be committed
    numpy scalars (np.int32/np.float32): a weak-typed python int would
    lower a different program than the one traffic calls."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.result_type(a)),
        tree)


def aot_compile(fn, arg_specs, *, donate_argnums=(), out_shardings=None):
    """Lower + compile ``fn`` for one EXACT argument signature, ahead of
    traffic (the Predictor bucket-cache discipline, factored out for
    engines that manage their own executables — the generation engine's
    donated decode step).  ``arg_specs`` are ShapeDtypeStructs (or
    pytrees of them, e.g. from ``spec_tree``); ``donate_argnums`` is
    forwarded to jax.jit, so a donated state argument keeps its
    buffer-reuse contract in the compiled executable.

    The two halves run under start-up's recorder
    (``utils.profiler.startup()``): ``lower`` (tracing and MLIR) and
    ``compile`` (XLA, or a load from the persistent cache) under the
    innermost open start-up scope, which is the executable's row
    ``build/<name>`` that the caller opened around this call.  The
    lowering is called from this frame, not through a helper or a
    lambda: that form cost a model's prompt passes half again their
    lowering time on the chip machine (PERF.md section 6, PR 36).

    Calling the result with a mismatched shape/dtype raises instead of
    recompiling — steady-state serving performs zero XLA compiles, and a
    signature drift is a loud error rather than a silent compile storm.

    ``out_shardings`` (optional, a pytree of NamedShardings matching the
    outputs) pins result placements — the layout-aware generation engine
    passes its state shardings so a donated, tp-sharded decode state
    comes back exactly where it went in (donation requires in == out).
    """
    if out_shardings is None:
        jitted = jax.jit(fn, donate_argnums=donate_argnums)
    else:
        jitted = jax.jit(fn, donate_argnums=donate_argnums,
                         out_shardings=out_shardings)
    boot = _profiler.startup()
    with boot.scope(boot.under("lower")):
        lowered = jitted.lower(*arg_specs)
    with boot.scope(boot.under("compile")):
        return lowered.compile()


def symbolic_input_specs(manifest_shapes, dtypes):
    """ShapeDtypeStructs for export: dims marked -1 become symbolic
    (jax.export) so the served artifact accepts any size there; returns
    None when every dim is concrete."""
    if not any(d < 0 for shp in manifest_shapes for d in shp):
        return None
    scope = jax.export.SymbolicScope()
    specs = []
    for i, (shp, dt) in enumerate(zip(manifest_shapes, dtypes)):
        dims = ",".join(f"d{i}_{j}" if d < 0 else str(d)
                        for j, d in enumerate(shp))
        shape = jax.export.symbolic_shape(dims, scope=scope)
        specs.append(jax.ShapeDtypeStruct(shape, np.dtype(dt)))
    return specs


def write_export_artifacts(path_prefix, exported, input_names,
                           manifest_shapes, dtypes, aot_params=None):
    """Serialize a jax.export.Exported + manifest (+ AOT param payload)
    in the layout Predictor._load reads — the ONE writer both
    inference.save_inference_model and static.save_inference_model use."""
    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    with open(path_prefix + ".pdexport", "wb") as f:
        f.write(exported.serialize())
    if aot_params is not None:
        with open(path_prefix + ".pdaotparams", "wb") as f:
            pickle.dump(aot_params, f)
    manifest = {
        "input_names": list(input_names),
        "output_names": [f"out{i}"
                         for i in range(len(exported.out_avals))],
        "input_specs": [{"shape": list(shp), "dtype": str(np.dtype(dt))}
                        for shp, dt in zip(manifest_shapes, dtypes)],
        "format": "jax.export/stablehlo",
    }
    with open(path_prefix + ".pdmodel.json", "w") as f:
        json.dump(manifest, f, indent=2)
    return path_prefix


def save_inference_model(path_prefix, layer_or_feed, fetch_vars=None,
                         input_spec=None, example_inputs=None):
    """Export a Layer for serving.

    TPU form: save_inference_model(prefix, layer, example_inputs=[...])
    — AOT-serializes the jitted forward (StableHLO) + weights + manifest.
    The fluid (executor, feed_names, fetch_targets) signature is accepted
    via paddle_tpu.distributed.fleet.save_inference_model.
    Reference: fluid io.py save_inference_model:1198.
    """
    from ..nn.layer_base import Layer, functional_call, state_pytrees

    layer = layer_or_feed
    if not isinstance(layer, Layer):
        raise TypeError("save_inference_model expects a Layer; for the "
                        "fluid executor signature use fleet.save_inference_model")
    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    was_training = layer.training
    layer.eval()
    try:
        params, buffers = state_pytrees(layer)

        # Dynamic dims (-1/None) in an InputSpec export symbolically via
        # jax.export so the served artifact accepts ANY size there. Baking
        # -1 to a concrete 1 (the old behavior) silently served batch-1
        # only (advisor r1/r2 finding).
        sym_in_specs = None
        manifest_shapes = None
        if input_spec is not None and example_inputs is not None:
            if len(input_spec) != len(example_inputs):
                raise ValueError(
                    f"input_spec has {len(input_spec)} entries but "
                    f"example_inputs has {len(example_inputs)}")
            for i, (s, a) in enumerate(zip(input_spec, example_inputs)):
                ashape = tuple(np.shape(np.asarray(
                    a.numpy() if isinstance(a, Tensor) else a)))
                if len(s.shape) != len(ashape) or any(
                        d is not None and d >= 0 and d != ad
                        for d, ad in zip(s.shape, ashape)):
                    raise ValueError(
                        f"input_spec[{i}] shape {list(s.shape)} does not "
                        f"match example_inputs[{i}] shape {list(ashape)}")
        if input_spec is not None:
            manifest_shapes = [[-1 if (d is None or d < 0) else int(d)
                                for d in s.shape] for s in input_spec]
            sym_in_specs = symbolic_input_specs(
                manifest_shapes,
                [convert_dtype(s.dtype) for s in input_spec])
        if example_inputs is None and input_spec is not None:
            example_inputs = [
                np.zeros([d if d and d > 0 else 1 for d in s.shape],
                         convert_dtype(s.dtype)) for s in input_spec]
        from .. import jit as _jit
        _jit.save(layer, path_prefix)  # .pdmodel + .pdiparams (full state)
        # AOT arg payload: PARAMS ONLY — buffers are baked into the
        # exported graph as constants, so the .call() arg structure must
        # match exactly (a buffer-carrying model, e.g. BN or QAT scales,
        # would otherwise mismatch the exported pytree)
        with open(path_prefix + ".pdaotparams", "wb") as f:
            pickle.dump({k: np.asarray(v) for k, v in params.items()}, f)

        if example_inputs is None:
            return path_prefix

        # export compiles the forward, so data-dependent python control
        # flow must be AST-converted here exactly as @to_static would
        # (otherwise an eager-trained model with `if tensor:` branches
        # fails at trace time); no-op when nothing converts
        import types

        from ..jit import _maybe_convert

        cls_fwd = type(layer).forward
        conv_fwd = _maybe_convert(cls_fwd)
        if conv_fwd is not cls_fwd and "forward" not in layer.__dict__:
            layer.forward = types.MethodType(conv_fwd, layer)
            converted_patch = True
        else:
            converted_patch = False

        def fwd(*flat):
            n_par = len(jax.tree.leaves(params))
            par = jax.tree.unflatten(jax.tree.structure(params),
                                     flat[:n_par])
            args = flat[n_par:]
            out, _ = functional_call(layer, par,
                                     tuple(Tensor(a) for a in args),
                                     buffers=buffers)
            if isinstance(out, (tuple, list)):
                return tuple(o.value for o in out)
            return (out.value,)

        arrays = [jnp.asarray(np.asarray(
            x.numpy() if isinstance(x, Tensor) else x))
            for x in example_inputs]
        in_specs = sym_in_specs if sym_in_specs is not None else [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in jax.tree.leaves(params)] + list(in_specs)
        try:
            exported = jax.export.export(jax.jit(fwd))(*specs)
        except Exception as e:
            if sym_in_specs is not None:
                raise ValueError(
                    "AOT export with dynamic dims "
                    f"{[list(s.shape) for s in sym_in_specs]} failed "
                    "(model not traceable with symbolic shapes: "
                    f"{type(e).__name__}: {e}). Pass concrete "
                    "example_inputs to export a fixed-shape artifact."
                ) from e
            raise
        return write_export_artifacts(
            path_prefix, exported, [f"x{i}" for i in range(len(arrays))],
            (manifest_shapes if manifest_shapes
             else [list(a.shape) for a in arrays]),
            [a.dtype for a in arrays])
    finally:
        if locals().get("converted_patch"):
            layer.__dict__.pop("forward", None)
        if was_training:
            layer.train()


def load_inference_model(path_prefix, executor=None):
    """Returns a Predictor (the fluid triple (program, feed, fetch) has no
    TPU analog — the predictor IS the optimized program).
    Reference: fluid io.py load_inference_model:1411."""
    return Predictor(Config(path_prefix))
