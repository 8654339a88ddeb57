"""A pool of distinct token minibatches for a language-model training cell.

Mix: ``batch`` rows a minibatch, each a sequence of ``seq`` tokens,
``pool_batches`` minibatches; token ids uniform over the vocabulary rows the
configuration holds, labels the next token."""
import numpy as np

from chipbench import loadgen


def generate(mix, seed, first_id, ids):
    """(pool, arrays): the minibatches in the reader protocol (a list of
    per-sample (tokens[seq], labels[seq]) tuples, each a view of one
    array), and the same as (tokens [batch, seq], labels [batch, seq])
    arrays for the reference. Ids lie in ``first_id .. first_id + ids - 1``."""
    rng = loadgen.rng(seed, 2)
    batch, seq = int(mix["batch"]), int(mix["seq"])
    pool, arrays = [], []
    for _ in range(int(mix["pool_batches"])):
        drawn = rng.integers(first_id, first_id + ids, (batch, seq + 1),
                             dtype=np.int64)
        tokens = np.ascontiguousarray(drawn[:, :-1])
        labels = np.ascontiguousarray(drawn[:, 1:])
        pool.append([(tokens[i], labels[i]) for i in range(batch)])
        arrays.append((tokens, labels))
    return pool, arrays
