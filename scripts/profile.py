"""One profiling entry point (supersedes profile_dp.py, profile_dp2.py,
profile_resnet.py, resnet_roofline.py, trace_resnet.py — all folded in
here as subcommands). Every MFU number is computed through the
hardware-truth cost model (``observability.profiler.CostModel``: XLA's
own flops/bytes for the exact compiled step), never bespoke math.

Usage:
  python scripts/profile.py hlo       [--skip-trace]    # step HLO + MFU
  python scripts/profile.py trace     [outdir]          # fit-window trace
  python scripts/profile.py roofline  [batch] [--write] # analytic BN/residual roofline
  python scripts/profile.py dp                          # dp_scaling decomposition
  python scripts/profile.py dp2                         # dp step-composition sweep

Knobs: ``RN_BATCH`` (hlo batch, default 128), ``DL4J_TPU_PEAK_FLOPS``
/ ``DL4J_TPU_PEAK_BYTES_PER_SEC`` (state the roofline on CPU).
"""
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


# -- hlo: optimized step HLO + cost-model MFU ---------------------------

def cmd_hlo(argv):
    """Dump the optimized HLO of the exact bench train step (layouts,
    transpose/copy counts, dtype mix), time the step, and report MFU
    from the step's own XLA cost analysis."""
    import jax

    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.observability import profiler
    from deeplearning4j_tpu.zoo import resnet50

    batch = int(os.environ.get("RN_BATCH", "128"))
    g = ComputationGraph(
        resnet50(dtype="bfloat16", learning_rate=0.01)
    ).init()
    g.scan_chunk = 1
    rng = np.random.RandomState(0)
    ds = DataSet(
        features=rng.randint(0, 256, (batch, 3, 224, 224),
                             dtype=np.uint8),
        labels=np.eye(1000, dtype=np.uint8)[
            rng.randint(0, 1000, batch)
        ],
    )
    g.fit_minibatch(ds)  # compile + 1 step
    _ = float(g.score_value)
    step_fn = g._jit_step
    if step_fn is None:
        print("no _jit_step; falling back to timing only")
    else:
        import jax.numpy as jnp

        dtype = g._dtype()
        inputs = [jnp.asarray(ds.features, dtype)]
        labels = [jnp.asarray(ds.labels, dtype)]
        lrs = {
            k: jnp.asarray(v, jnp.float32)
            for k, v in g.updater_def.scheduled_lrs(
                g.iteration_count
            ).items()
        }
        t = jnp.asarray(g.iteration_count + 1, jnp.float32)
        key = jax.random.fold_in(g._base_key, g.iteration_count)
        try:
            txt = step_fn.lower(
                g.params, g.updater_state, g.state, inputs, labels,
                None, None, lrs, t, key,
            ).compile().as_text()
        except Exception as e:
            txt = None
            print("HLO lowering failed:", repr(e))
        if txt:
            out = os.path.join("artifacts", "resnet50_hlo.txt")
            os.makedirs("artifacts", exist_ok=True)
            with open(out, "w") as f:
                f.write(txt)
            ops = re.findall(r"^\s*%?\S+ = (\S+?)\(", txt, re.M)
            from collections import Counter

            c = Counter(
                re.sub(r"\..*", "", re.sub(r"\(.*", "", o))
                for o in ops
            )
            interesting = {
                k: v for k, v in c.items()
                if any(s in k for s in (
                    "transpose", "copy", "convolution", "fusion",
                    "all-reduce", "reduce", "dot",
                ))
            }
            print("HLO op histogram (interesting):", interesting)
            convs = re.findall(
                r"= (\S+)\[([^\]]*)\]\{([^}]*)\} convolution", txt
            )
            print("conv output dtype/shape/layout (first 5):",
                  convs[:5])
            print("HLO written to", out)

    # step timing + hardware-truth MFU
    for _ in range(2):
        g.fit_minibatch(ds)
    _ = float(g.score_value)
    times = []
    for _ in range(6):
        t0 = time.perf_counter()
        g.fit_minibatch(ds)
        _ = float(g.score_value)
        times.append(time.perf_counter() - t0)
    step_s = min(times)
    cm = profiler.train_step_cost_model(g, ds)
    peak, kind = profiler.peak_flops()
    peak_bw, _ = profiler.peak_bytes_per_sec()
    got = cm.achieved(step_s, peak)
    print(f"step {step_s * 1000:.1f} ms  batch {batch}  "
          f"{batch / step_s:.1f} ex/s")
    print(f"cost model {cm.key}: {cm.flops / 1e9:.1f} GFLOP, "
          f"{cm.bytes_accessed / 1e9:.2f} GB, "
          f"AI {cm.arithmetic_intensity:.1f} flop/byte")
    if got["mfu"] is not None:
        print(f"MFU {got['mfu']:.4f} against {kind} peak "
              f"{peak / 1e12:.1f} TFLOP/s "
              f"(roofline class: "
              f"{profiler.ROOFLINE_NAMES[cm.roofline_class(peak, peak_bw)]})")
    else:
        print("MFU undefined: no peak FLOP/s for this device "
              "(set DL4J_TPU_PEAK_FLOPS)")

    if "--skip-trace" not in argv:
        trace_dir = os.path.join("artifacts", "resnet50_trace_hlo")
        jax.profiler.start_trace(trace_dir)
        for _ in range(3):
            g.fit_minibatch(ds)
        _ = float(g.score_value)
        jax.profiler.stop_trace()
        print("trace written to", trace_dir)


# -- trace: fit-window profiler capture ---------------------------------

def cmd_trace(argv):
    """Capture a jax profiler trace of the exact bench ResNet-50 fit
    window (HBM-resident batches, scan-fused steps); parse with
    scripts/parse_trace.py."""
    outdir = argv[0] if argv else "artifacts/resnet50_trace_r6"
    import jax

    from bench import _to_hbm
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo import resnet50

    batch, chunk = 128, 2
    g = ComputationGraph(
        resnet50(dtype="bfloat16", learning_rate=0.01)
    ).init()
    g.scan_chunk = chunk
    rng = np.random.RandomState(0)
    batches = _to_hbm([
        DataSet(
            features=rng.randint(0, 256, (batch, 3, 224, 224),
                                 dtype=np.uint8),
            labels=np.eye(1000, dtype=np.uint8)[
                rng.randint(0, 1000, batch)
            ],
        )
        for _ in range(chunk)
    ])
    g.fit(batches, epochs=1)  # compile
    _ = float(g.score_value)
    jax.profiler.start_trace(outdir)
    g.fit(batches, epochs=3)
    _ = float(g.score_value)
    jax.profiler.stop_trace()
    print("trace written to", outdir)


# -- roofline: analytic BN/residual traffic model -----------------------

ROOFLINE_ARTIFACT = os.path.join("artifacts",
                                 "resnet50_roofline_r6.md")


def roofline_model(batch: int) -> dict:
    """Train-mode memory traffic of every non-conv pass over the real
    zoo shapes. Pass model per BN layer over activation bytes S
    (bf16): fwd 1-read stats + read/write apply (3S); bwd dy+x
    multi-output reductions (2S) + dx read-read-write (3S). Residual
    adds: 5S. Maxpool bwd and the loss tail are excluded (measured
    separately in the trace)."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.zoo import resnet50

    conf = resnet50(dtype="bfloat16", learning_rate=0.01)
    it = InputType.convolutional(224, 224, 3)
    types = {}
    bn_bytes = 0.0
    res_bytes = 0.0
    n_bn = 0
    n_add = 0
    for name in conf.topological_order():
        v = conf.vertices[name]
        ins = conf.vertex_inputs.get(name, ())
        in_t = types[ins[0]] if ins and ins[0] in types else it
        lc = getattr(v, "layer_conf", None)
        out_t = lc.output_type(in_t) if lc is not None else in_t
        types[name] = out_t
        kind = (type(lc).__name__ if lc is not None
                else type(v).__name__)
        if kind == "BatchNormalization":
            s = (batch * out_t.channels * out_t.height * out_t.width
                 * 2)  # bf16
            bn_bytes += 8 * s
            n_bn += 1
        elif "ElementWise" in kind:
            s = (batch * out_t.channels * out_t.height * out_t.width
                 * 2)
            res_bytes += 5 * s
            n_add += 1
    return {"batch": batch, "n_bn": n_bn, "n_add": n_add,
            "bn_bytes": bn_bytes, "res_bytes": res_bytes,
            "total_bytes": bn_bytes + res_bytes}


def cmd_roofline(argv):
    from deeplearning4j_tpu.observability import profiler

    args = [a for a in argv if not a.startswith("--")]
    batch = int(args[0]) if args else 128
    peak_bw, bw_kind = profiler.peak_bytes_per_sec()
    if peak_bw is None:
        raise SystemExit(
            f"roofline: no peak HBM bytes/s for {bw_kind!r} — run on "
            "the chip, or state a roofline with DL4J_TPU_PEAK_BYTES_PER_SEC"
        )
    m = roofline_model(batch)
    t_ms = m["total_bytes"] / peak_bw * 1e3
    lines = [
        f"batch {batch}: {m['n_bn']} BN layers, "
        f"{m['n_add']} residual adds",
        f"BN traffic       {m['bn_bytes'] / 1e9:7.2f} GB",
        f"residual traffic {m['res_bytes'] / 1e9:7.2f} GB",
        f"total            {m['total_bytes'] / 1e9:7.2f} GB "
        f"-> {t_ms:.2f} ms at {peak_bw / 1e9:.0f} GB/s ({bw_kind})",
    ]
    print("\n".join(lines))
    if "--write" in argv:
        os.makedirs("artifacts", exist_ok=True)
        with open(ROOFLINE_ARTIFACT, "w") as f:
            f.write(
                "# ResNet-50 non-conv roofline (regenerated by "
                "`scripts/profile.py roofline`)\n\n"
                "Analytic HBM floor of the non-conv passes over the "
                "real zoo shapes.\nPass model per BN layer over "
                "activation bytes S (bf16): fwd 1-read\nstats + "
                "read/write apply (3S); bwd dy+x multi-output "
                "reductions (2S)\n+ dx read-read-write (3S); "
                "residual adds 5S. Measured context and\nthe "
                "fusion-share argument live in "
                "`resnet50_roofline_r5.md`.\n\n```\n"
                + "\n".join(lines) + "\n```\n"
            )
        print("written to", ROOFLINE_ARTIFACT)


# -- dp / dp2: data-parallel scaling attribution ------------------------
# Both run their measurements in child processes on an 8-device
# virtual CPU mesh (XLA_FLAGS host platform device count), so the
# parent's jax is never initialized with the wrong topology.

_DP_CHILD = r"""
import json, os, time
import numpy as np
from __graft_entry__ import _ensure_devices
_ensure_devices(8)
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from deeplearning4j_tpu.parallel.compat import shard_map_compat
shard_map = shard_map_compat()
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel import build_mesh
from deeplearning4j_tpu.zoo import resnet50

n = int(os.environ["DP_DEVICES"])
b = int(os.environ["DP_BATCH"])
steps = int(os.environ.get("DP_STEPS", "3"))
what = os.environ["DP_WHAT"]  # step | fwdbwd | pmean | update

conf = resnet50(height=32, width=32, channels=3, n_classes=10,
                cifar_stem=True, learning_rate=0.01)
net = ComputationGraph(conf).init()
mesh = build_mesh(data=n, model=1, devices=jax.devices()[:n])
updater = net.updater_def
rep_sh = NamedSharding(mesh, P())
dp_sh = NamedSharding(mesh, P("data"))

params = jax.device_put(net.params, rep_sh)
upd = jax.tree_util.tree_map(lambda a: jax.device_put(a, rep_sh),
                             net.updater_state)
state = jax.tree_util.tree_map(lambda a: jax.device_put(a, rep_sh),
                               net.state)
rng = jax.random.PRNGKey(0)
lrs = {k: jnp.asarray(v, jnp.float32)
       for k, v in updater.scheduled_lrs(0).items()}
t = jnp.asarray(1.0, jnp.float32)
rs = np.random.RandomState(0)
x = jax.device_put(rs.rand(b, 3, 32, 32).astype(np.float32), dp_sh)
y = jax.device_put(
    np.eye(10, dtype=np.float32)[rs.randint(0, 10, b)], dp_sh)

rep = P(); dp = P("data")

def time_fn(fn, args):
    out = fn(*args)          # compile + 1 run
    jax.block_until_ready(out)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return min(times)

if what == "step":
    def step(params, upd, state, x, y, lrs, t, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        def loss_fn(p):
            s, ns = net._score_pure(p, state, [x], [y], None, rng,
                                    train=True, fmasks=None)
            return s, ns
        (score, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = jax.lax.pmean(grads, "data")
        score = jax.lax.pmean(score, "data")
        new_params, new_upd = updater.update(grads, upd, params, lrs, t)
        new_state = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "data"), new_state)
        return new_params, new_upd, new_state, score
    f = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(rep, rep, rep, dp, dp, rep, rep, rep),
                          out_specs=(rep, rep, rep, rep),
                          check_rep=False))
    sec = time_fn(f, (params, upd, state, x, y, lrs, t, rng))
elif what == "fwdbwd":
    def step(params, state, x, y, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        def loss_fn(p):
            s, ns = net._score_pure(p, state, [x], [y], None, rng,
                                    train=True, fmasks=None)
            return s, ns
        (score, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return grads, new_state, score
    f = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(rep, rep, dp, dp, rep),
                          out_specs=(rep, rep, rep),
                          check_rep=False))
    sec = time_fn(f, (params, state, x, y, rng))
elif what == "pmean":
    def red(g, s):
        g = jax.lax.pmean(g, "data")
        s = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, "data"), s)
        return g, s
    f = jax.jit(shard_map(red, mesh=mesh, in_specs=(rep, rep),
                          out_specs=(rep, rep), check_rep=False))
    sec = time_fn(f, (params, state))
elif what == "update":
    def up(g, upd, params, lrs, t):
        return updater.update(g, upd, params, lrs, t)
    f = jax.jit(shard_map(up, mesh=mesh,
                          in_specs=(rep, rep, rep, rep, rep),
                          out_specs=(rep, rep), check_rep=False))
    sec = time_fn(f, (params, upd, params, lrs, t))
print(json.dumps({"what": what, "devices": n, "batch": b,
                  "sec": sec}))
"""

_DP2_CHILD = r"""
import json, os, time
import numpy as np
from __graft_entry__ import _ensure_devices
_ensure_devices(8)
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from deeplearning4j_tpu.parallel.compat import shard_map_compat
shard_map = shard_map_compat()
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel import build_mesh
from deeplearning4j_tpu.zoo import resnet50

n = int(os.environ["DP_DEVICES"])
b = int(os.environ["DP_BATCH"])
steps = int(os.environ.get("DP_STEPS", "3"))
variant = os.environ["DP_VARIANT"]

conf = resnet50(height=32, width=32, channels=3, n_classes=10,
                cifar_stem=True, learning_rate=0.01)
net = ComputationGraph(conf).init()
mesh = build_mesh(data=n, model=1, devices=jax.devices()[:n])
updater = net.updater_def
rep_sh = NamedSharding(mesh, P())
dp_sh = NamedSharding(mesh, P("data"))

def place(tree, sh):
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)

rng = jax.random.PRNGKey(0)
lrs = {k: jnp.asarray(v, jnp.float32)
       for k, v in updater.scheduled_lrs(0).items()}
t = jnp.asarray(1.0, jnp.float32)
rs = np.random.RandomState(0)
x_h = rs.rand(b, 3, 32, 32).astype(np.float32)
y_h = np.eye(10, dtype=np.float32)[rs.randint(0, 10, b)]

rep = P(); dp = P("data")

def flat_pmean(tree, axis):
    # ONE fused all-reduce: DDP-style gradient bucketing
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate(
        [l.astype(jnp.float32).ravel() for l in leaves])
    flat = jax.lax.pmean(flat, axis)
    out, off = [], 0
    for l, s in zip(leaves, sizes):
        out.append(flat[off:off + s].reshape(l.shape).astype(l.dtype))
        off += s
    return jax.tree_util.tree_unflatten(treedef, out)

def make_step(state_mode, joint, flat):
    def step(params, upd, state, x, y, lrs, t, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
        def loss_fn(p):
            s, ns = net._score_pure(p, state, [x], [y], None, rng,
                                    train=True, fmasks=None)
            return s, ns
        (score, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if flat:
            red = (grads, score, new_state if state_mode == "pmean"
                   else None)
            grads, score, red_state = flat_pmean(red, "data")
            if state_mode == "pmean":
                new_state = red_state
        elif joint:
            to_red = (grads, score, new_state if state_mode == "pmean"
                      else None)
            grads, score, red_state = jax.lax.pmean(to_red, "data")
            if state_mode == "pmean":
                new_state = red_state
        else:
            grads = jax.lax.pmean(grads, "data")
            score = jax.lax.pmean(score, "data")
            if state_mode == "pmean":
                new_state = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, "data"), new_state)
        new_params, new_upd = updater.update(grads, upd, params, lrs, t)
        return new_params, new_upd, new_state, score
    return step

def build(variant):
    donate = "donate" in variant
    state_mode = "local" if "nostate" in variant else "pmean"
    joint = "joint" in variant
    flat = "flat" in variant
    if variant.startswith("gspmd"):
        def step(params, upd, state, x, y, lrs, t, rng):
            def loss_fn(p):
                s, ns = net._score_pure(p, state, [x], [y], None, rng,
                                        train=True, fmasks=None)
                return s, ns
            (score, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_upd = updater.update(
                grads, upd, params, lrs, t)
            return new_params, new_upd, new_state, score
        return jax.jit(
            step,
            in_shardings=(rep_sh, rep_sh, rep_sh, dp_sh, dp_sh,
                          None, None, None),
            out_shardings=(rep_sh, rep_sh, rep_sh, rep_sh),
            donate_argnums=(0, 1, 2) if donate else (),
        )
    f = shard_map(make_step(state_mode, joint, flat), mesh=mesh,
                  in_specs=(rep, rep, rep, dp, dp, rep, rep, rep),
                  out_specs=(rep, rep, rep, rep), check_rep=False)
    return jax.jit(f, donate_argnums=(0, 1, 2) if donate else ())

f = build(variant)
# host-side master copies: donation deletes the placed device arrays,
# so each iteration re-places from host
params_h = jax.tree_util.tree_map(np.asarray, net.params)
upd_h = jax.tree_util.tree_map(np.asarray, net.updater_state)
state_h = jax.tree_util.tree_map(np.asarray, net.state)
times = []
for it in range(steps + 1):
    params = place(params_h, rep_sh)
    upd = place(upd_h, rep_sh)
    state = place(state_h, rep_sh)
    x = jax.device_put(x_h, dp_sh); y = jax.device_put(y_h, dp_sh)
    jax.block_until_ready((params, upd, state, x, y))
    t0 = time.perf_counter()
    out = f(params, upd, state, x, y, lrs, t, rng)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    if it > 0:  # first = compile
        times.append(dt)
    del out
print(json.dumps({"variant": variant, "devices": n, "batch": b,
                  "sec": min(times)}))
"""


def _run_child(child_src, tag, extra_env, steps=3):
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=8"
                      ).strip(),
        "DP_STEPS": str(steps),
        "PYTHONPATH": REPO,
    })
    env.update(extra_env)
    t0 = time.time()
    out = subprocess.run([sys.executable, "-c", child_src], env=env,
                         capture_output=True, text=True, timeout=3600)
    wall = time.time() - t0
    if out.returncode != 0:
        return {**tag, "error": out.stderr[-1500:],
                "wall": round(wall, 1)}
    r = json.loads(out.stdout.strip().splitlines()[-1])
    r["wall"] = round(wall, 1)
    return r


def cmd_dp(argv):
    """Attribute dp_scaling overhead: full step vs collectives alone
    vs updater alone, n=1 vs n=8 on the virtual mesh."""
    results = []
    for what, n, b in [
        ("step", 1, 8), ("step", 8, 64),
        ("fwdbwd", 1, 8), ("fwdbwd", 8, 64),
        ("pmean", 8, 64),
        ("update", 1, 8), ("update", 8, 64),
    ]:
        r = _run_child(
            _DP_CHILD, {"what": what, "devices": n, "batch": b},
            {"DP_DEVICES": str(n), "DP_BATCH": str(b),
             "DP_WHAT": what},
        )
        results.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({"all": results}))


def cmd_dp2(argv):
    """Sweep step compositions: donation, state-pmean placement,
    joint-vs-split pmean, GSPMD vs shard_map."""
    for variant, n, b in [
        ("plain", 8, 64),
        ("donate", 8, 64),
        ("flat", 8, 64),
        ("flat_donate", 8, 64),
        ("joint", 8, 64),
        ("nostate", 8, 64),
        ("gspmd_donate", 8, 64),
        ("donate", 1, 8),
        ("flat_donate", 1, 8),
    ]:
        print(json.dumps(_run_child(
            _DP2_CHILD, {"variant": variant, "devices": n, "batch": b},
            {"DP_DEVICES": str(n), "DP_BATCH": str(b),
             "DP_VARIANT": variant},
        )), flush=True)


COMMANDS = {
    "hlo": cmd_hlo,
    "trace": cmd_trace,
    "roofline": cmd_roofline,
    "dp": cmd_dp,
    "dp2": cmd_dp2,
}


def main():
    if len(sys.argv) < 2 or sys.argv[1] not in COMMANDS:
        print(__doc__)
        return 2
    return COMMANDS[sys.argv[1]](sys.argv[2:]) or 0


if __name__ == "__main__":
    sys.exit(main())
