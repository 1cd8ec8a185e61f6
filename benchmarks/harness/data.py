"""One general generator of host batches, driven by the ``input`` block
of a configuration's file and the seed. The same seed gives the same
batches; every batch of a set differs from the others."""

import numpy as np

from deeplearning4j_tpu.datasets import DataSet


def seed_rng(seed, stream):
    """``numpy`` generator for one use of the seed (any whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def make_batches(spec, batch, n, seed):
    """``n`` DataSets of ``batch`` rows in float32, as an input
    pipeline would deliver them to ``fit()``."""
    rng = seed_rng(seed, 1)
    kind = spec["kind"]
    out = []
    for _ in range(n):
        if kind == "image":
            c, h, w = spec["shape"]
            x = rng.standard_normal((batch, c, h, w), dtype=np.float32)
            classes = rng.integers(0, spec["classes"], batch)
            y = np.eye(spec["classes"], dtype=np.float32)[classes]
        elif kind == "onehot_sequence":
            v, t = spec["vocab"], spec["length"]
            # a fixed skewed byte distribution, so that there is
            # something to learn beyond the uniform ln(vocab)
            p = 1.0 / (np.arange(v) + spec.get("skew_offset", 10.0))
            ids = rng.choice(v, size=(batch, t + 1), p=p / p.sum())
            if spec.get("row_roll"):
                # every row favours bytes of its own (the distribution
                # rolled by the row's place in the batch), so a batch
                # with rows left out is another batch, not a noisier
                # draw of the same one
                ids = (ids + (np.arange(batch) * v // batch)[:, None]) % v
            eye = np.eye(v, dtype=np.float32)
            x = np.ascontiguousarray(
                eye[ids[:, :-1]].transpose(0, 2, 1))   # [b, v, t]
            y = np.ascontiguousarray(eye[ids[:, 1:]].transpose(0, 2, 1))
        else:
            raise ValueError(f"unknown input kind {kind!r}")
        out.append(DataSet(features=x, labels=y))
    return out
