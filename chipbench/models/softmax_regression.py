"""Softmax regression (MNIST-LR): the scoring function, its plain
reference, the on-device frame and the needed work.

Source: rowhit/tensorframes v0.2.8, the frozen-graph scoring path
(``src/main/python/tensorframes/core.py:41-55``) over the MNIST softmax
regression of the TensorFlow tutorial: ``argmax(x @ W + b)`` with ``x``
``[rows, 784]`` float32, ``W`` ``[784, 10]``, ``b`` ``[10]``. Departures:
random features and weights from the seed (features N(0, 1), weights
N(0, 2/784) as ``MLPClassifier.init`` draws them, bias N(0, 0.01)), not the
MNIST images or trained weights. The source computes in float32, so the
scoring function asks for ``highest`` matmul precision; the control is the
same product at ``high`` (three bfloat16 passes), written out.
"""

import numpy as np


def init_weights(seed, cfg):
    """``W`` and ``b`` as host float32 arrays (31 KB: the scoring function
    closes over them, as a frozen graph holds its constants)."""
    rng = np.random.default_rng(int(seed))
    n_in, n_out = cfg["features"], cfg["classes"]
    w = rng.normal(0.0, (2.0 / n_in) ** 0.5, (n_in, n_out))
    b = rng.normal(0.0, 0.1, (n_out,))
    return w.astype(np.float32), b.astype(np.float32)


def make_features(seed, rows, cfg):
    """The feature column, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(np.uint32(int(seed) % (2**32)))
    make = jax.jit(
        lambda k: jax.random.normal(k, (rows, cfg["features"]), jnp.float32)
    )
    return make(key)


def score_fn(w, b, precision="highest"):
    """The function handed to ``tft.map_blocks``: the column name is the
    argument's name, the result's key the new column's."""
    import jax.numpy as jnp

    def score(features):
        logits = jnp.matmul(features, w, precision=precision) + b
        return {
            "prediction": jnp.argmax(logits, axis=-1).astype(jnp.int32),
            "score": jnp.max(logits, axis=-1),
        }

    return score


def reference_gap(x, w, b, predictions, scores, block_rows=131072):
    """Over all rows: the widest gap by which the predicted class's
    reference logit lies below the reference's best; the rows whose
    prediction is not the reference's first; and the widest distance
    between the score the program gave and the reference logit of the
    class it predicted. The reference is the same product in float32 at
    ``highest``, block of rows by block."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(xb, pb, sb):
        logits = jnp.matmul(xb, w, precision="highest") + b
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, pb[:, None], axis=-1)[:, 0]
        flips = jnp.sum(pb != jnp.argmax(logits, axis=-1))
        return jnp.max(best - got), flips, jnp.max(jnp.abs(sb - got))

    rows = x.shape[0]
    worst, flips, off = 0.0, 0, 0.0
    for lo in range(0, rows, block_rows):
        hi = min(rows, lo + block_rows)
        g, f, e = gap(x[lo:hi], predictions[lo:hi], scores[lo:hi])
        worst, flips, off = max(worst, float(g)), flips + int(f), max(off, float(e))
    return worst, flips, off


def control_predictions(x, w, b, block_rows=131072):
    """The control: the scoring function in the program's place, one
    precision below the configuration's. ``high`` is three bfloat16
    passes: each operand split into a bfloat16 head and a bfloat16 rest,
    head x head + head x rest + rest x head, accumulated in float32. It is
    written out here so that it is the same arithmetic on any backend."""
    import jax
    import jax.numpy as jnp

    def split(a):
        # reduce_precision, not astype: XLA may keep excess precision
        # through a float32 -> bfloat16 -> float32 round trip, and the
        # rest would then be zero
        head = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        rest = jax.lax.reduce_precision(a - head, exponent_bits=8, mantissa_bits=7)
        return head.astype(jnp.bfloat16), rest.astype(jnp.bfloat16)

    w_head, w_rest = split(jnp.asarray(w))
    dot = lambda a, c: jnp.matmul(a, c, preferred_element_type=jnp.float32)

    @jax.jit
    def score(xb):
        head, rest = split(xb)
        logits = dot(head, w_head) + dot(head, w_rest) + dot(rest, w_head) + b
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), jnp.max(logits, axis=-1)

    rows = x.shape[0]
    parts = [score(x[lo : lo + block_rows]) for lo in range(0, rows, block_rows)]
    return (
        jnp.concatenate([p for p, _ in parts]),
        jnp.concatenate([s for _, s in parts]),
    )


def row_flops(cfg):
    return 2 * cfg["features"] * cfg["classes"]


def pass_bytes(cfg, rows):
    """Least bytes of one scoring pass: the features read once, the
    predictions and their scores written once."""
    return rows * (cfg["features"] * 4 + 8)
