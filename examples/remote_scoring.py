"""Serve a frozen model from the TPU host; remote workers stream Arrow.

The reference ran its engine inside every Spark executor (compute went
to the partitions because every executor had CPU TensorFlow). TPUs
invert that: executors have no chips, so partitions come to the
accelerator. This example runs the full inverted pattern in one process
tree:

1. the TPU host starts a :class:`ScoringServer` over a captured scoring
   program (weights frozen into the program at trace time);
2. "executors" — here worker threads, in production Spark tasks via
   ``remote_map_in_arrow(spark_df, addr, schema)`` — connect with ONLY
   socket + pyarrow and stream their partition as one Arrow IPC
   connection each;
3. results stream back; each connection's rows formed one logical
   block, so cross-row programs see partition semantics.

Run: python examples/remote_scoring.py
"""

import threading

import numpy as np
import pyarrow as pa

from tensorframes_tpu.interop import ScoringServer, remote_arrow_mapper


def main():
    rng = np.random.default_rng(0)
    n_features, n_parts, rows_per_part = 32, 4, 5000
    w = rng.normal(size=(n_features,)).astype(np.float32)

    def score(features):
        # frozen at trace time, exactly like the reference's
        # variable-freezing (core.py:41-55); also a cross-row stat to
        # prove partition semantics survive the wire
        s = features @ w
        return {"score": s, "rank_in_partition": s.argsort().argsort()}

    parts = [
        rng.normal(size=(rows_per_part, n_features)).astype(np.float32)
        for _ in range(n_parts)
    ]

    results = [None] * n_parts
    with ScoringServer(score, feed_dict={"features": "x"}) as addr:
        print(f"serving on {addr}")
        fn = remote_arrow_mapper(addr)  # what Spark would pickle to tasks

        def executor(i):
            table = pa.table({
                "x": pa.FixedSizeListArray.from_arrays(
                    pa.array(parts[i].ravel(), type=pa.float32()),
                    n_features,
                )
            })
            results[i] = pa.Table.from_batches(
                list(fn(table.to_batches(max_chunksize=512)))
            )

        threads = [
            threading.Thread(target=executor, args=(i,))
            for i in range(n_parts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    total = 0
    for i, out in enumerate(results):
        scores = out.column("score").to_numpy()
        ranks = out.column("rank_in_partition").to_numpy()
        # rtol sized for the MXU's default bf16-pass f32 matmuls
        # (~2e-3 rel vs the numpy f64 oracle)
        np.testing.assert_allclose(scores, parts[i] @ w, rtol=5e-3, atol=1e-3)
        # the rank column proves the whole partition formed one block
        assert sorted(ranks) == list(range(rows_per_part))
        total += len(scores)
    print(f"scored {total} rows across {n_parts} remote partitions; "
          f"partition-block semantics verified")


if __name__ == "__main__":
    main()
