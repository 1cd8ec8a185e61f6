#!/usr/bin/env python3
"""One ``RoutedExperts`` layer alone on the chip: the row ladder against
the form it replaced, at several held shares.

    chiprun -- python3 scripts/routed_experts_ab.py

The layer is ``glm47flash.fit_4k``'s (2 x 4,096 tokens of 2,048, 64
experts of width 1,536 of which 8 are held, top 4, bfloat16, no shared
expert: both sides would compute the same one). A bias on the held
experts' selection, found by bisection on the chip, steers the share of
the 32,768 token-slots that the held experts are sent to each of
``--shares`` (per cent; 12.5 is even routing, 100 every slot: the last
rung). Per share it times one call, forward alone and forward with its
backward (``jax.vjp`` with a random cotangent: output, dx and the three
expert stacks' gradients), of two sides in this one process, turn about:

- ``L`` — ``RoutedExperts.apply`` as the package has it: the data path
  over the first rung of ``row_ladder`` that holds the live rows;
- ``P`` — the form before the ladder, kept here as the yardstick: every
  token repeated ``top_k`` times, all ``n * top_k`` sorted slots
  gathered, multiplied, masked and gathered back.

Both sides are compared on the chip with the dense combine in float32
at the highest precision (every held expert over every token, weighted
by the same routing; ``rel_err``: out, dx, dEg, dEu, dEd), because the
chip's grouped kernel leaves the rows behind the last group unwritten
in its backward pass and no CPU run can show a mask that is missing: a
side past ``--tolerance`` is printed as ``[fault]`` and the exit code
is 2. Times are host-clock, ``--reps`` calls then
``block_until_ready``, per call, the median of ``--rounds``. Exits
non-zero where JAX finds no TPU (``--rehearse``: a tiny layer on any
device; nothing it prints is a device number). The table goes to
``chiprun_out/routed_experts_ab.json`` too.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# batch, time, width, expert width, experts, held, top k
CELL = (2, 4096, 2048, 1536, 64, 8, 4)
REHEARSAL = (2, 256, 32, 16, 16, 2, 2)


def routing(layer, params, x, state):
    """What ``RoutedExperts.apply`` works out before its data path."""
    import jax
    import jax.numpy as jnp

    tokens = x.reshape(-1, x.shape[-1])
    first, last = layer.held()
    g = last - first + 1
    chosen, w = layer.route(params, tokens, state["route_bias"])
    here = (chosen >= first) & (chosen <= last)
    key = jnp.where(here, chosen - first, g).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, g, dtype=jnp.int32), axis=0)
    return tokens, chosen, w, here, order, jnp.argsort(order), sizes


def before_the_ladder(layer, params, x, state):
    """The layer's output as ``apply`` gave it up to PR 35."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def permute(a, perm, inverse):
        return a[perm]

    permute.defvjp(lambda a, perm, inverse: (a[perm], inverse),
                   lambda inverse, g: (g[inverse], None, None))

    tokens, _, w, here, order, inverse, sizes = routing(
        layer, params, x, state)
    n, k = tokens.shape[0], layer.top_k
    live = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]

    def dot(a, e):
        return jnp.where(live, jax.lax.ragged_dot(
            jnp.where(live, a, 0), params[e], sizes), 0)

    rows = permute(jnp.repeat(tokens, k, axis=0), order, inverse)
    out = dot(jax.nn.silu(dot(rows, "Eg")) * dot(rows, "Eu"), "Ed")
    out = permute(out, inverse, order).reshape(n, k, -1)
    y = jnp.sum(out.astype(jnp.float32) * jnp.where(here, w, 0.0)[..., None],
                axis=1).astype(x.dtype)
    return y.reshape(x.shape)


def dense_float32(layer, params, x, chosen):
    """Every held expert over every token in float32, weighted by the
    routing weights of ``chosen`` (the sides' own selection: a float32
    router could break a near tie the other way)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    tokens = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(jnp.dot(tokens, params["router"], precision=hi))
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if layer.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * layer.scaling
    first, last = layer.held()
    y = jnp.zeros_like(tokens)
    for e in range(first, last + 1):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        eg, eu, ed = (params[name][e - first] for name in ("Eg", "Eu", "Ed"))
        y = y + mine[:, None] * jnp.dot(
            jax.nn.silu(jnp.dot(tokens, eg, precision=hi))
            * jnp.dot(tokens, eu, precision=hi), ed, precision=hi)
    return y.reshape(x.shape)


def bias_for_share(layer, params, x, share):
    """A selection bias on the held experts under which they are sent
    ``share`` of the token-slots (by bisection; the share that came out
    is returned beside it)."""
    import jax
    import jax.numpy as jnp

    first, last = layer.held()
    held = (jnp.arange(layer.n_experts) >= first) & (
        jnp.arange(layer.n_experts) <= last)

    @jax.jit
    def share_at(delta):
        chosen, _ = layer.route(params, x.reshape(-1, x.shape[-1]),
                                jnp.where(held, delta, 0.0))
        return jnp.mean((chosen >= first) & (chosen <= last))

    lo, hi = -2.0, 2.0
    for _ in range(30):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if float(share_at(mid)) < share else (lo, mid)
    return jnp.where(held, hi, 0.0), float(share_at(hi))


def time_ms(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / reps


def rel(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shares", default="3,6,12.5,30,100")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=0.05)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import RoutedExperts

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({dev.platform}): nothing to measure",
              file=sys.stderr)
        return 1
    b, t, d, f, n_experts, held, k = REHEARSAL if args.rehearse else CELL
    layer = RoutedExperts(n_in=d, hidden_size=f, n_experts=n_experts,
                          held_first=0, held_last=held - 1, top_k=k,
                          n_shared=0, scaling=1.8)
    keys = jax.random.split(jax.random.PRNGKey(36), 3)
    shapes = jax.eval_shape(layer.init_params, keys[0])
    params = {name: (jax.random.normal(key, v.shape) * 0.02).astype(
                  jnp.bfloat16)
              for (name, v), key in zip(
                  shapes.items(), jax.random.split(keys[0], len(shapes)))}
    exact = {name: v.astype(jnp.float32) for name, v in params.items()}
    x = jax.random.normal(keys[1], (b, t, d)).astype(jnp.bfloat16)
    probe = jax.random.normal(keys[2], (b, t, d)).astype(jnp.bfloat16)
    stacks = ("Eg", "Eu", "Ed")

    def side(fn):
        def forward(p, x, state):
            return fn(layer, p, x, state)

        def both(p, x, state, g):
            out, vjp = jax.vjp(
                lambda x_, *e: fn(layer, {**p, **dict(zip(stacks, e))},
                                  x_, state), x, *(p[n] for n in stacks))
            return (out, *vjp(g))

        return jax.jit(forward), jax.jit(both)

    sides = {
        "L": side(lambda layer, p, x, st: layer.apply(p, x, st)[0]),
        "P": side(before_the_ladder),
    }

    @jax.jit
    def truth(p, x, chosen, g):
        out, vjp = jax.vjp(
            lambda x_, *e: dense_float32(
                layer, {**p, **dict(zip(stacks, e))}, x_, chosen),
            x, *(p[n] for n in stacks))
        return (out, *vjp(g))

    print(f"device {dev.device_kind} ({dev.platform}); layer "
          f"[{b}, {t}, {d}] x {held} of {n_experts} experts of {f}, "
          f"top {k}; rungs {layer.rungs(b * t * k)}; ms a call "
          f"(host clock), forward / forward with backward")
    rows, fault = [], False
    for want in (float(s) for s in args.shares.split(",")):
        state = layer.init_state()
        state["route_bias"], share = bias_for_share(
            layer, params, x, want / 100)
        _, new_state = jax.jit(layer.apply)(params, x, state)
        chosen = jax.jit(lambda p, x, st: routing(layer, p, x, st)[1])(
            params, x, state)
        exact_out = truth(exact, x.astype(jnp.float32), chosen,
                          probe.astype(jnp.float32))
        row = {"share_asked": want, "share": 100 * share,
               "rung_calls": new_state["rung_calls"].tolist(),
               "dropped": int(new_state["dropped"])}
        times = {name: ([], []) for name in sides}
        for _ in range(args.rounds):
            for name, (forward, both) in sides.items():
                times[name][0].append(
                    time_ms(forward, (params, x, state), args.reps))
                times[name][1].append(
                    time_ms(both, (params, x, state, probe), args.reps))
        for name, (_, both) in sides.items():
            errs = [rel(a, e) for a, e in zip(
                both(params, x, state, probe), exact_out)]
            bad = not all(e <= args.tolerance for e in errs)
            fault = fault or bad or row["dropped"] != 0
            row[name] = {
                "fwd_ms": statistics.median(times[name][0]),
                "fwd_bwd_ms": statistics.median(times[name][1]),
                "rel_err": errs, "fault": bad}
        rows.append(row)
        print(f"held share {row['share']:6.2f}% rung_calls "
              f"{row['rung_calls']} dropped {row['dropped']}: " + "; ".join(
                  f"{name} {row[name]['fwd_ms']:.3f} / "
                  f"{row[name]['fwd_bwd_ms']:.3f} rel_err "
                  + " ".join(f"{e:.4f}" for e in row[name]["rel_err"])
                  + (" [fault]" if row[name]["fault"] else "")
                  for name in sides), flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "routed_experts_ab.json"), "w") as fh:
        json.dump({"device": dev.device_kind, "platform": dev.platform,
                   "rehearsal": args.rehearse, "reps": args.reps,
                   "rounds": args.rounds, "rows": rows}, fh, indent=1)
    return 2 if fault else 0


if __name__ == "__main__":
    sys.exit(main())
