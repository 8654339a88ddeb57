"""LSTM sequence-model benchmark (IMDB-style text classification).

reference harness: benchmark/paddle/rnn/rnn.py (2-layer LSTM, bs/hid
sweeps; 184 ms/batch at bs64 h512 on K40m per BASELINE.md).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.lod import build_lod_tensor


def bench(batch_size=64, hidden=512, seq_len=100, vocab=30000, layers_n=2,
          iters=10, warmup=2):
    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    words = layers.data("words", shape=[1], dtype="int64", lod_level=1)
    label = layers.data("label", shape=[1], dtype="int64")
    emb = layers.embedding(input=words, size=[vocab, hidden])
    inp = emb
    for i in range(layers_n):
        proj = layers.fc(input=inp, size=hidden * 4)
        h, _ = layers.dynamic_lstm(input=proj, size=hidden * 4,
                                   is_reverse=(i % 2 == 1))
        inp = h
    pooled = layers.sequence_pool(input=inp, pool_type="max")
    pred = layers.fc(input=pooled, size=2, act="softmax")
    loss = layers.mean(layers.cross_entropy(input=pred, label=label))
    pt.Adam(learning_rate=0.002).minimize(loss)

    exe = pt.Executor(pt.TPUPlace())
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, vocab, (seq_len, 1)).astype("int64")
            for _ in range(batch_size)]
    feed = exe.prepare_feed({
        "words": build_lod_tensor(seqs),
        "label": rng.randint(0, 2, (batch_size, 1)).astype("int64")})
    for _ in range(max(warmup, 1)):
        out, = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    np.asarray(out)  # sync: the warm-up has finished on the device
    best = float("inf")
    for _ in range(3):  # best-of-3 windows
        t0 = time.perf_counter()
        for _ in range(iters):
            out, = exe.run(feed=feed, fetch_list=[loss],
                           return_numpy=False)
        np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    tokens = batch_size * seq_len
    return {"model": "lstm%dx%d" % (layers_n, hidden),
            "batch_size": batch_size, "seq_len": seq_len,
            "ms_per_batch": round(best * 1e3, 2),
            "tokens_per_sec": round(tokens / best, 2)}


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--seq_len", type=int, default=100)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()
    print(json.dumps(bench(args.batch_size, args.hidden, args.seq_len,
                           layers_n=args.layers, iters=args.iters)))
