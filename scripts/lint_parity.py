#!/usr/bin/env python
"""Static check: both engine wrappers delegate their hot paths to the
unified functional core (``deeplearning4j_tpu/nn/core.py``).

History: ``MultiLayerNetwork`` and ``ComputationGraph`` each carried a
private copy of the train-step builder, the scan-fused multi-step,
the pretrain step, and the fit drivers — every perf PR paid its tax
twice, and the copies drifted. The core refactor collapsed them; this
lint keeps them collapsed:

1. Both engine modules must import ``deeplearning4j_tpu.nn.core``.
2. Neither engine module may call the primitives that define a hot
   path of its own: ``value_and_grad`` / ``grad`` (a private backward
   pass), ``lax.scan`` / ``checkpoint`` / ``remat`` (a private
   whole-net transform), ``updater.update`` outside the core, or any
   cross-device collective (``psum`` / ``all_gather`` /
   ``psum_scatter`` — collectives live only in ``parallel/`` and
   ``nn/core.py``; an engine that grows one has re-inlined a
   distribution concern, e.g. the ZeRO all-gather).
3. The core must actually define the shared machinery the engines
   claim to delegate to (``build_step``, ``build_multi_step``,
   ``build_pretrain_step``, ``apply_layer_run``, ``fit_batches``).
4. Both engine classes must still expose the delegating methods the
   rest of the stack calls (``_build_step``, ``_build_multi_step``,
   ``fit_minibatch``, ``output``).

Pure AST scan — nothing is imported, so this runs in milliseconds in
any environment (part of the ``scripts/run_chaos.sh`` preamble next
to ``lint_metrics.py``).

Exit 0 when the split holds; exit 1 with the exact violations.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
NN = REPO / "deeplearning4j_tpu" / "nn"
ENGINES = {
    "MultiLayerNetwork": NN / "multilayer.py",
    "ComputationGraph": NN / "graph.py",
}
CORE = NN / "core.py"

# calling any of these inside an engine module means a duplicate hot
# path grew back (the backward pass, a scan fusion, or a remat wrap
# that belongs in the core)
FORBIDDEN_CALLS = {"value_and_grad", "scan", "checkpoint", "remat"}
# cross-device collectives: distribution (grad psum, the ZeRO state
# all-gather, reduce-scatter variants) lives in parallel/ + nn/core.py
# only — an engine file growing one of these has re-inlined it
FORBIDDEN_COLLECTIVES = {
    "psum", "all_gather", "all_gather_invariant", "psum_scatter",
}
# plus updater.update(...) — the optimizer application site
FORBIDDEN_METHOD_ON = {"update": {"updater", "upd_def", "updater_def"}}

CORE_REQUIRED = {
    "build_step", "build_multi_step", "build_pretrain_step",
    "apply_layer_run", "maybe_remat", "fit_batches", "run_scan_chunk",
    "apply_step_out", "build_megastep", "run_megastep_chunk",
    "megastep_readback", "fit_epoch_megastep",
}

# megastep contract: the per-chunk driver loop in nn/core.py owns ONE
# designated host-readback site (megastep_readback). Any other host
# sync inside the drivers silently turns the fused K-step dispatch
# back into K round trips — the exact regression the megastep exists
# to kill, and invisible to correctness tests (trajectory unchanged,
# only dispatches/step bloats).
MEGASTEP_DRIVERS = {
    "run_megastep_chunk", "fit_epoch_megastep", "flush_megastep",
}
MEGASTEP_FORBIDDEN = {
    "block_until_ready", "device_get", "item", "tolist", "asarray",
    "copy_to_host_async",
}
ENGINE_REQUIRED_METHODS = {
    "_build_step", "_build_multi_step", "fit_minibatch", "output",
}


def call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def call_base(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return f.value.id
    return ""


def check_engine(name: str, path: Path, errors: list) -> None:
    tree = ast.parse(path.read_text(), filename=str(path))
    imports_core = any(
        (isinstance(n, ast.ImportFrom)
         and n.module == "deeplearning4j_tpu.nn"
         and any(a.name == "core" for a in n.names))
        or (isinstance(n, ast.ImportFrom)
            and n.module == "deeplearning4j_tpu.nn.core")
        or (isinstance(n, ast.Import)
            and any(a.name == "deeplearning4j_tpu.nn.core"
                    for a in n.names))
        for n in ast.walk(tree)
    )
    if not imports_core:
        errors.append(
            f"{path.name}: does not import deeplearning4j_tpu.nn.core"
        )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node)
        base = call_base(node)
        if base == "core":
            continue  # delegation to the core is the point
        if cn in FORBIDDEN_CALLS:
            errors.append(
                f"{path.name}:{node.lineno}: calls {cn}() — the "
                "backward pass / scan fusion / remat belongs in "
                "nn/core.py"
            )
        if cn in FORBIDDEN_COLLECTIVES:
            errors.append(
                f"{path.name}:{node.lineno}: calls {cn}() — "
                "collectives live only in parallel/ + nn/core.py"
            )
        bases = FORBIDDEN_METHOD_ON.get(cn)
        if bases and base in bases:
            errors.append(
                f"{path.name}:{node.lineno}: calls {base}.{cn}() — "
                "optimizer application belongs in nn/core.py"
            )
    # the engine class must still expose the delegating surface
    cls = next(
        (n for n in tree.body
         if isinstance(n, ast.ClassDef) and n.name == name), None,
    )
    if cls is None:
        errors.append(f"{path.name}: class {name} not found")
        return
    methods = {
        n.name for n in cls.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for m in sorted(ENGINE_REQUIRED_METHODS - methods):
        errors.append(
            f"{path.name}: {name} lost its delegating method {m}()"
        )


def check_pallas_locality(errors: list) -> None:
    """All Pallas entry points live in ``deeplearning4j_tpu/ops/`` and
    go through the dispatch gate. A layer (or any other) module calling
    ``pl.pallas_call`` directly has grown a private kernel outside the
    library: it bypasses ``dispatch.use_pallas()``/``pallas_interpret``
    (the off-TPU interpreter arming) and the dispatch metrics."""
    pkg = REPO / "deeplearning4j_tpu"
    ops_dir = pkg / "ops"
    for path in sorted(pkg.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        calls_pallas = [
            node.lineno for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and call_name(node) == "pallas_call"
        ]
        if not calls_pallas:
            continue
        if ops_dir not in path.parents:
            errors.append(
                f"{path.relative_to(REPO)}:{calls_pallas[0]}: calls "
                "pallas_call() outside deeplearning4j_tpu/ops/ — "
                "Pallas kernels live in the ops/ library behind "
                "dispatch.use_pallas()"
            )
            continue
        # an ops kernel module must reference the dispatch gate (its
        # public wrappers resolve interpret/use_pallas before the call)
        names = {
            n.attr if isinstance(n, ast.Attribute) else
            getattr(n, "id", "")
            for n in ast.walk(tree)
            if isinstance(n, (ast.Attribute, ast.Name))
        }
        if not names & {"use_pallas", "pallas_interpret"}:
            errors.append(
                f"{path.relative_to(REPO)}: calls pallas_call() but "
                "never consults dispatch.use_pallas()/"
                "pallas_interpret() — forced-on CPU runs would crash "
                "in Mosaic lowering instead of interpreting"
            )


TILING_OWNERS = {"tiling.py", "autotune.py"}
# legacy per-module block pickers the tiling refactor deleted; one of
# these reappearing means a kernel grew a private divisor heuristic
# the autotuner can't see (its candidate space and the dispatch
# heuristic would disagree about feasibility)
LEGACY_PICKERS = {
    "_pick_blocks", "_seq_batch_block", "_divisors_desc",
    "_largest_divisor_leq",
}


def check_tiling_locality(errors: list) -> None:
    """Block-size selection for the Pallas kernels lives ONLY in
    ``ops/tiling.py`` (VMEM budget, divisor heuristics, candidate
    enumeration) and ``ops/autotune.py`` (measured winners over that
    same candidate space). A kernel module doing its own inline
    divisor math (the ``%`` operator) or re-growing a private picker
    forks the feasibility rules: the heuristic, the tuner's candidate
    space, and the ``*_ok`` routing gates drift apart, and a persisted
    tuning entry can validate against one rule set and dispatch under
    another. (String ``%``-formatting is exempt; blocked-grid
    ``//`` arithmetic is fine — only divisibility/remainder tests are
    selection logic.)"""
    ops_dir = REPO / "deeplearning4j_tpu" / "ops"
    for path in sorted(ops_dir.glob("*.py")):
        if path.name in TILING_OWNERS:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Mod)
                    and not (isinstance(node.left, ast.Constant)
                             and isinstance(node.left.value, str))):
                errors.append(
                    f"ops/{path.name}:{node.lineno}: inline '%' "
                    "remainder math — block feasibility/selection "
                    "lives in ops/tiling.py (+ measured winners in "
                    "ops/autotune.py)"
                )
            if (isinstance(node,
                           (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in LEGACY_PICKERS):
                errors.append(
                    f"ops/{path.name}:{node.lineno}: defines "
                    f"{node.name}() — a private block picker grew "
                    "back; extend ops/tiling.py instead"
                )
        calls_pallas = any(
            isinstance(n, ast.Call) and call_name(n) == "pallas_call"
            for n in ast.walk(tree)
        )
        if not calls_pallas:
            continue
        names = {
            n.attr if isinstance(n, ast.Attribute) else
            getattr(n, "id", "")
            for n in ast.walk(tree)
            if isinstance(n, (ast.Attribute, ast.Name))
        }
        if not names & {"tiling", "autotune"}:
            errors.append(
                f"ops/{path.name}: calls pallas_call() but never "
                "consults ops.tiling/ops.autotune — its block "
                "configs come from somewhere private"
            )


def check_megastep_readback(errors: list) -> None:
    """The megastep driver functions may not read device values
    except through the single ``megastep_readback()`` call — one
    blocking host sync per K-step chunk, at the designated site.
    (``float()``/``bool()`` on the ALREADY-read-back host dict are
    fine and not flagged; ``device_get``/``block_until_ready``/
    ``.item()``/``.tolist()``/``asarray`` inside a driver are not.)"""
    tree = ast.parse(CORE.read_text(), filename=str(CORE))
    drivers = {
        n.name: n for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and n.name in MEGASTEP_DRIVERS
    }
    for fn in sorted(MEGASTEP_DRIVERS - set(drivers)):
        errors.append(
            f"core.py: megastep driver {fn}() not found — the "
            "readback-site lint has nothing to protect"
        )
    readback_calls = []
    for fn_name, fn in drivers.items():
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node)
            if cn == "megastep_readback":
                readback_calls.append((fn_name, node.lineno))
            elif cn in MEGASTEP_FORBIDDEN:
                errors.append(
                    f"core.py:{node.lineno}: {fn_name}() calls "
                    f"{cn}() — megastep drivers must not touch the "
                    "device outside the single megastep_readback() "
                    "site (one host sync per chunk)"
                )
    if drivers and len(readback_calls) != 1:
        sites = ", ".join(
            f"{f}:{ln}" for f, ln in readback_calls) or "none"
        errors.append(
            "core.py: expected exactly ONE megastep_readback() call "
            f"across the megastep drivers, found {len(readback_calls)}"
            f" ({sites}) — the per-chunk readback has one designated "
            "site in run_megastep_chunk()"
        )
    elif readback_calls and readback_calls[0][0] != "run_megastep_chunk":
        errors.append(
            f"core.py:{readback_calls[0][1]}: the megastep_readback() "
            "site moved out of run_megastep_chunk() — keep the "
            "designated readback in the chunk driver"
        )


def check_embedding_locality(errors: list) -> None:
    """Collective-locality rules for the sharded embeddings subsystem.

    1. Raw cross-device collectives (``FORBIDDEN_COLLECTIVES``) may be
       CALLED only under ``parallel/``, in ``nn/core.py``, or in
       ``embeddings/table.py`` — the subsystem's one designated
       collective site. A workload module (``embeddings/word2vec.py``,
       ``embeddings/deepwalk.py``) growing its own psum has re-inlined
       the exchange the table owns; anywhere else it has re-inlined a
       distribution concern (same rationale as the engine rule above).
    2. ``segment_sum`` — the sparse scatter-add primitive — may be
       called only in ``embeddings/`` + ``nn/core.py``: a layer or
       workload summing duplicate-id gradients itself bypasses the
       dedup contract (PAD_ID padding, sorted-order determinism) the
       cross-mesh bitwise tests pin down in ``embeddings/sparse.py``.
    3. ``embeddings/table.py`` must consult the shard_map machinery it
       claims to ride (``shard_map_compat`` from ``parallel/compat``)
       — a raw ``jax.shard_map``/``Mesh`` context grown there would
       bypass the version-compat shim every other mesh program uses.
    """
    pkg = REPO / "deeplearning4j_tpu"
    emb_dir = pkg / "embeddings"
    table_py = emb_dir / "table.py"
    collective_ok = lambda p: (  # noqa: E731
        (pkg / "parallel") in p.parents
        or p == CORE
        or p == table_py
    )
    for path in sorted(pkg.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node)
            if cn in FORBIDDEN_COLLECTIVES and not collective_ok(path):
                errors.append(
                    f"{path.relative_to(REPO)}:{node.lineno}: calls "
                    f"{cn}() — raw collectives live only in parallel/, "
                    "nn/core.py, and embeddings/table.py (the "
                    "subsystem's designated collective site)"
                )
            if cn == "segment_sum" and not (
                emb_dir in path.parents or path == CORE
            ):
                errors.append(
                    f"{path.relative_to(REPO)}:{node.lineno}: calls "
                    "segment_sum() — sparse scatter-add dedup lives in "
                    "embeddings/ (+ nn/core.py); use "
                    "embeddings.sparse.dedup_segment_sum"
                )
    if table_py.exists():
        tree = ast.parse(table_py.read_text(), filename=str(table_py))
        names = {
            n.attr if isinstance(n, ast.Attribute) else
            getattr(n, "id", "")
            for n in ast.walk(tree)
            if isinstance(n, (ast.Attribute, ast.Name))
        }
        if "shard_map_compat" not in names:
            errors.append(
                "embeddings/table.py: never consults "
                "shard_map_compat() — mesh programs ride the "
                "parallel/compat shim, not a raw shard_map"
            )
    else:
        errors.append(
            "embeddings/table.py: missing — the collective-locality "
            "rule has nothing to protect"
        )


def check_core(errors: list) -> None:
    tree = ast.parse(CORE.read_text(), filename=str(CORE))
    defined = {
        n.name for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for fn in sorted(CORE_REQUIRED - defined):
        errors.append(
            f"core.py: missing shared implementation {fn}() — the "
            "engines have nothing to delegate to"
        )


def main() -> int:
    errors: list = []
    check_core(errors)
    check_megastep_readback(errors)
    for name, path in ENGINES.items():
        check_engine(name, path, errors)
    check_pallas_locality(errors)
    check_tiling_locality(errors)
    check_embedding_locality(errors)
    if errors:
        print("engine/core parity violations:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print(
        "lint_parity: both engines delegate step/apply/fit hot paths "
        "to nn/core.py; Pallas kernels stay in ops/ behind dispatch; "
        "block selection stays in ops/tiling.py + ops/autotune.py; "
        "megastep drivers keep one readback site; embedding "
        "collectives stay in embeddings/table.py"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
