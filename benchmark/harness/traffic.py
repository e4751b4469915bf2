"""The one general generator: a traffic mix is a data file it reads.

For a training mix (``"kind": "train"``) the traffic is the table the
boosting loop is fed: ``rows x features`` float32 and a binary label. The
columns are standard normal, every ``heavy_tail_every``-th one
``|x| ** heavy_tail_power`` (positive, heavy-tailed; after
``bench.make_higgs_like``); the label is the sign of a linear form over
``informative`` columns, one ``sin(a) * b`` interaction and normal noise, so
that trees have something to learn.

The table's values come from the mix's ``table_seed``; ``--seed`` draws the
order of its columns. A boosting iteration's work is set by the trees it
grows, which differ from table to table by 3% of an iteration's time; the
same table with its columns in another order grows the same trees (a
feature's histogram does not depend on where the feature stands), so every
seed gives the same work and the runs' spread is the machine's.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 16384
THREADS = 8


def make_table(rows: int, features: int, seed: int, data: dict):
    """``(X float32 [rows, features], y float32 [rows])``: the table of
    ``data["table_seed"]`` with its columns in the order ``seed`` draws."""
    root = np.random.SeedSequence([int(data["table_seed"]), rows, features])
    label_seq, *chunk_seqs = root.spawn(1 + -(-rows // CHUNK_ROWS))
    order = np.random.Generator(np.random.Philox(
        np.random.SeedSequence(int(seed)))).permutation(features)
    X = np.empty((rows, features), dtype=np.float32)
    every = int(data["heavy_tail_every"])
    power = float(data["heavy_tail_power"])

    def fill(i):
        block = X[i * CHUNK_ROWS:(i + 1) * CHUNK_ROWS]
        gen = np.random.Generator(np.random.Philox(chunk_seqs[i]))
        gen.standard_normal(out=block, dtype=np.float32)
        block[:, ::every] = np.abs(block[:, ::every]) ** power
        block[:] = block[:, order]

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(len(chunk_seqs))))

    gen = np.random.Generator(np.random.Philox(label_seq))
    k = min(int(data["informative"]), features)
    # the informative columns of the table, where ``order`` has put them
    cols = np.argsort(order)[np.sort(gen.choice(features, k, replace=False))]
    w = (gen.standard_normal(k) * float(data["weight_scale"])).astype(
        np.float32)
    noise = gen.standard_normal(rows, dtype=np.float32) * np.float32(
        data["noise"])
    informative = X[:, cols]
    logit = informative @ w + np.float32(data["interaction"]) * np.sin(
        informative[:, 0]) * informative[:, -1] + noise
    # centre, so that both classes are about as frequent whatever w is
    y = (logit > np.median(logit)).astype(np.float32)
    return X, y
