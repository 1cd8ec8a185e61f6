#!/usr/bin/env python3
"""``compile_described.py`` for a cell of the ``fit_tokens`` driver:
compile the window program (the scan of ``scan_chunk`` optimizer steps
on ids and integer labels) for a described, unattached v5e and print
what the chip's compiler says of its memory. No weight is made: the
shapes come from ``jax.eval_shape``. Costs no chip time; nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_described_tokens.py \
        --workload glm47flash.fit_4k [--batch 1] [--remat dots_saveable]

A compile that passes is not a chip run and is never reported as one.
"""

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def compile_scan_program(workload, chip, *, rehearse=False, batch=None,
                         remat=None):
    """``(compiled, net, batch)``: the cell's scan-of-``scan_chunk``
    program compiled for the described device of ``chip`` (a
    ``SingleDeviceSharding``). The caller steers
    ``ops.dispatch.effective_platform`` (and turns the compilation
    cache off) around it."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.spec import Cell, load_module

    driver = load_module("drivers", "fit_tokens")
    cell = Cell(workload)
    cfg = driver.sized(cell.config, rehearse)
    traffic = driver.sized(cell.traffic, rehearse)
    batch = batch or traffic["batch"]
    if remat:
        cfg = dict(cfg, program=dict(cfg["program"], kwargs=dict(
            cfg["program"]["kwargs"], remat=remat)))
    net = driver.build_program(cfg, 0)

    def shapes():
        net.init()
        return net.params, net.updater_state, net.state

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=chip), tree)

    params, upd, state = shaped(jax.eval_shape(shapes))
    net.params = net.updater_state = None
    k, t = net.scan_chunk, cfg["input"]["length"]
    ahead = driver.labels_ahead(cfg)
    x = jax.ShapeDtypeStruct((k, batch, t), jnp.uint16, sharding=chip)
    y = jax.ShapeDtypeStruct((k, batch, t + ahead - 1), jnp.uint16,
                             sharding=chip)
    lrs = {n: jax.ShapeDtypeStruct((k,), jnp.float32, sharding=chip)
           for n in net.updater_def.settings}
    it0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    key = jax.ShapeDtypeStruct(net._base_key.shape, net._base_key.dtype,
                               sharding=chip)
    compiled = net._build_multi_step().lower(
        params, upd, state, x, y, None, None, lrs, it0, key).compile()
    return compiled, net, batch


def main(argv=None):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.ops import dispatch

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--hlo", default=None, help="write the HLO text here")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    dispatch.effective_platform = lambda: "tpu"  # route as on the chip
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    t0 = time.perf_counter()
    compiled, net, batch = compile_scan_program(
        args.workload, SingleDeviceSharding(topo.devices[0]),
        batch=args.batch, remat=args.remat)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    gib = 2 ** 30
    print(f"workload {args.workload} batch {batch} scan_chunk "
          f"{net.scan_chunk} remat {net.remat}: compiled for "
          f"{topo.devices[0].device_kind} in "
          f"{time.perf_counter() - t0:.1f} s (host seconds)")
    print(f"  arguments {mem.argument_size_in_bytes / gib:.3f} GiB, "
          f"outputs {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
          f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB")
    print(f"  arguments + temporaries "
          f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib:.3f}"
          f" GiB = "
          f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9:.3f}"
          f" GB; tpu_custom_call sites {text.count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
