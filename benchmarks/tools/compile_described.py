#!/usr/bin/env python3
"""Compile a ``fit`` cell's window program (the scan of ``scan_chunk``
optimizer steps, not the per-step one) for a described, unattached
v5e and print what the chip's compiler says of its memory. Costs no
chip time; nothing runs. Run it here, on the CPU:

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_described.py \
        --workload resnet50.fit [--batch 64]

A compile that passes is not a chip run and is never reported as one.
"""

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.drivers.fit import build_program, sized
    from benchmarks.harness.spec import Cell
    from deeplearning4j_tpu.ops import dispatch

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    cfg, traffic = sized(cell.config, False), sized(cell.traffic, False)
    batch = args.batch or traffic["batch"]

    jax.config.update("jax_enable_compilation_cache", False)
    dispatch.effective_platform = lambda: "tpu"  # route as on the chip
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=chip), tree)

    net = build_program(cfg, 0).init()
    k = net.scan_chunk
    spec = cfg["input"]
    if spec["kind"] == "image":
        x_shape = (k, batch, *spec["shape"])
        y_shape = (k, batch, spec["classes"])
    else:
        x_shape = y_shape = (k, batch, spec["vocab"], spec["length"])
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32, sharding=chip)
    y = jax.ShapeDtypeStruct(y_shape, jnp.float32, sharding=chip)
    is_graph = hasattr(net.conf, "vertices")
    xs, ys = ([x], [y]) if is_graph else (x, y)
    lrs = {n: jax.ShapeDtypeStruct((k,), jnp.float32, sharding=chip)
           for n in net.updater_def.settings}
    it0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    key = jax.ShapeDtypeStruct(net._base_key.shape, net._base_key.dtype,
                               sharding=chip)
    t0 = time.perf_counter()
    compiled = net._build_multi_step().lower(
        shaped(net.params), shaped(net.updater_state), shaped(net.state),
        xs, ys, None, None, lrs, it0, key,
    ).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2 ** 30
    print(f"workload {args.workload} batch {batch} scan_chunk {k}: "
          f"compiled for {topo.devices[0].device_kind} in "
          f"{time.perf_counter() - t0:.1f} s (host seconds)")
    print(f"  arguments {mem.argument_size_in_bytes / gib:.3f} GiB, "
          f"outputs {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
          f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB")
    print(f"  arguments + temporaries "
          f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib:.3f}"
          f" GiB; tpu_custom_call sites {text.count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
