#!/usr/bin/env python3
"""Device memory of a configuration's serving programs, compiled for a
described TPU v5e without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/aot_memory.py chipbench/configs/<name>.json

Compiles the engine's decode program at the full lane width (over the
gathered ``lanes x cache_len`` view) and its widest chunk program (one lane)
through the tuned Pallas matmul path, and prints each program's
``memory_analysis()``: arguments, outputs, temporaries, in bytes.  What the
eager pool copies between programs add is not in these numbers; the chip
run's ``memory_peak_bytes`` is.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(path: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro
    from chipbench.harness import arch_config
    from repro.models.model import build_model

    config = json.loads(Path(path).read_text())
    eng = config["engine"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = arch_config(config)
    model = build_model(cfg)
    rt = repro.tune([config["registry_arch"]], devices=("tpu_v5e",), seed=0).runtime(
        device="tpu_v5e", strict=True)
    rt.set_pallas_enabled(True)

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    params = placed(jax.eval_shape(model.init, jax.random.key(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    b, t = eng["max_batch"], eng["cache_len"]
    chunk = eng["prefill_chunk_tokens"]
    progs = {
        f"decode width {b}": (model.decode_step,
                              (params, placed(jax.eval_shape(lambda: model.init_cache(b, t))),
                               i32(b, 1), i32(b))),
        f"chunk {chunk}": (model.prefill_chunk,
                           (params, placed(jax.eval_shape(lambda: model.init_cache(1, t))),
                            i32(1, chunk), i32(), i32())),
    }
    for name, (fn, args) in progs.items():
        with rt.activate():
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        m = compiled.memory_analysis()
        print(json.dumps({"config": config["name"], "program": name,
                          "argument_bytes": m.argument_size_in_bytes,
                          "output_bytes": m.output_size_in_bytes,
                          "alias_bytes": m.alias_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes,
                          "pallas": "tpu_custom_call" in compiled.as_text()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
