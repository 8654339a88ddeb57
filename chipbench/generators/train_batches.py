"""A pool of distinct minibatches for a training cell.

Mix: ``batch`` rows a minibatch, ``pool_batches`` minibatches, pixels
N(0, 1) (images after the usual mean and deviation normalisation), labels
uniform over the classes."""
import numpy as np

from chipbench import loadgen


def generate(mix, seed, image, classes):
    """(pool, arrays): the minibatches in the reader protocol (a list of
    per-sample (image, [label]) tuples), and the same as arrays for the
    reference."""
    rng = loadgen.rng(seed, 1)
    batch = int(mix["batch"])
    pool, arrays = [], []
    for _ in range(int(mix["pool_batches"])):
        imgs = rng.standard_normal((batch, 3, image, image),
                                   dtype=np.float32)
        labels = rng.integers(0, classes, (batch, 1), dtype=np.int64)
        pool.append([(imgs[i], labels[i]) for i in range(batch)])
        arrays.append((imgs, labels[:, 0]))
    return pool, arrays
