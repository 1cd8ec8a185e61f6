"""Operations and bytes of ``resnet50_imagenet`` from its shapes: what
the algorithm needs, whatever implements it. Forward and backward of
the convolutions and the classifier; no batch norm, pooling or updater
(they are not matrix work), and nothing recomputed.

A convolution's backward pass is two more convolutions of the same
size (for the input and for the kernel); the first layer needs no
gradient for its input.
"""

BYTES = 2  # bfloat16 operands, as the configuration computes


def layers(model):
    """[(name, macs_forward, in_elems, out_elems, weight_elems,
    needs_input_grad)] per example, forward order."""
    rows = []
    base = model["base_width"]

    def conv(name, cin, cout, k, s, p, size, first=False):
        out = (size + 2 * p - k) // s + 1
        rows.append((name, cout * cin * k * k * out * out,
                     cin * size * size, cout * out * out,
                     cout * cin * k * k, not first))
        return out

    hw = conv("stem", model["channels"], base, 7, 2, 3, model["height"],
              first=True)
    hw = (hw + 2 - 3) // 2 + 1
    cin = base
    for stage, depth in enumerate(model["depths"]):
        width = base * 2 ** stage
        for block in range(depth):
            stride = 2 if (block == 0 and stage > 0) else 1
            n = f"s{stage}b{block}"
            if block == 0:
                conv(f"{n}_proj", cin, 4 * width, 1, stride, 0, hw)
            conv(f"{n}_c1", cin, width, 1, 1, 0, hw)
            mid = conv(f"{n}_c2", width, width, 3, stride, 1, hw)
            conv(f"{n}_c3", width, 4 * width, 1, 1, 0, mid)
            hw, cin = mid, 4 * width
    rows.append(("out", cin * model["n_classes"], cin,
                 model["n_classes"], cin * model["n_classes"], True))
    return rows


def forward_macs_per_example(cfg):
    return sum(r[1] for r in layers(cfg["model"]))


def flops_per_example(cfg):
    """Forward and backward floating-point operations of one example."""
    return sum(2 * macs * (3 if grad_in else 2)
               for _, macs, _, _, _, grad_in in layers(cfg["model"]))


def roofline_seconds_per_step(cfg, batch, peaks):
    """The least time one optimizer step's matrix work could take on
    the chip: per layer and per pass, the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, summed."""
    total = 0.0
    for _, macs, n_in, n_out, n_w, grad_in in layers(cfg["model"]):
        flops = 2 * macs * batch
        x, y, w = n_in * batch * BYTES, n_out * batch * BYTES, n_w * BYTES
        passes = [x + w + y, x + y + w]       # forward; kernel gradient
        if grad_in:
            passes.append(y + w + x)          # input gradient
        for moved in passes:
            total += max(flops / peaks["flops_bf16"],
                         moved / peaks["hbm_bytes_per_s"])
    return total
