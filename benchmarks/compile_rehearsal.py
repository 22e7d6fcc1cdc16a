#!/usr/bin/env python3
"""The third rehearsal: the real train step and the real decode steps,
compiled for a described TPU v5e, printing `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 benchmarks/compile_rehearsal.py \
        [--workload NAME ...]

No chip is attached and nothing runs: a pass says the chip's compiler
takes the program at the cell's real sizes and how many bytes the compiled
program wants, and nothing about results or times.  The program picks its
kernels by `jax.default_backend()`, which is the CPU here, so this script
(and only this script) answers "tpu" in its place while the steps are
traced; and it hands `aot_compile` the described device.  It counts one
program at a time: what else the process keeps on the device (weights,
Adam's state, the KV pools) is in `argument_size_in_bytes` of the program
that takes it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_ENABLE_X64", "0")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def analysis(compiled):
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["live_bytes_at_peak_estimate"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu import inference
    from benchmarks import common

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    manifest = common.load_manifest()
    for cell in manifest["workloads"]:
        if args.workload and cell["name"] not in args.workload:
            continue
        _, _, config, spec = common.resolve_cell(manifest, cell["name"])
        cfg = config["model"]
        ref = common.plugin("reference", config["reference"])
        adapter = common.plugin("adapters", config["adapter"])
        dtype = spec.get("weights_dtype", "float32")
        shapes = jax.eval_shape(
            lambda k: ref.init_weights(k, cfg, jnp.dtype(dtype)),
            jax.random.PRNGKey(0))
        weights = {n: jnp.zeros(s.shape, s.dtype) for n, s in shapes.items()}
        net = adapter.build_network(cfg, weights, dtype)
        real_backend = jax.default_backend
        out = {}
        if spec["kind"] == "fit":
            model = adapter.build_trainer(net, spec["optimizer"])
            from paddle_tpu.hapi.engine import TrainEngine

            engine = model._engine = TrainEngine(model)
            engine.begin()
            B, S = spec["batch"], spec["seq"]
            ids = on_chip([paddle.Tensor(jnp.zeros((B, S), jnp.int32))])
            net.train()
            jax.default_backend = lambda: "tpu"
            try:
                with paddle.amp.auto_cast(dtype=spec["autocast"]):
                    lowered = engine._step_fn.lower(
                        on_chip(engine.state),
                        on_chip(jax.random.PRNGKey(0)),
                        ids, ids)
            finally:
                jax.default_backend = real_backend
            compiled = lowered.compile()
            out["train_step"] = analysis(compiled)
            out["train_step"]["pallas_kernels"] = \
                compiled.as_text().count("tpu_custom_call")
        else:
            server, engine = adapter.build_server(net, spec["engine"], False)
            real_aot = inference.aot_compile
            wanted = ("decode_step", "target_prefill", "insert_prefix_step",
                      "_insert_prefix")

            class Skipped:
                out_info = None

                def __call__(self, *a):
                    raise RuntimeError("not compiled in the rehearsal")

            last_prefill = {}

            def aot(fn, arg_specs, *, donate_argnums=(), out_shardings=None):
                name = getattr(fn, "__name__", str(fn))
                if name not in wanted:
                    sk = Skipped()
                    sk.out_info = last_prefill.get("info")
                    return sk
                jitted = jax.jit(fn, donate_argnums=donate_argnums)
                c = jitted.lower(*on_chip(tuple(arg_specs))).compile()
                shape = next((tuple(a.shape) for a in
                              jax.tree_util.tree_leaves(arg_specs)
                              if len(a.shape) == 2 and a.shape[0] == 1), "")
                out[f"{name}{list(shape) if shape else ''}"] = analysis(c)
                if name == "decode_step":
                    out[name]["pallas_kernels"] = \
                        c.as_text().count("tpu_custom_call")
                if name == "target_prefill":
                    last_prefill["info"] = c.out_info
                return c

            inference.aot_compile = aot
            jax.default_backend = lambda: "tpu"
            try:
                engine.start()
            finally:
                jax.default_backend = real_backend
                inference.aot_compile = real_aot
                engine.stop()
        print(json.dumps({"rehearsal": "compile", "for": "described v5e:2x2, "
                          "one chip", "ran_on": real_backend(),
                          "workload": cell["name"], "programs": out}),
              flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
