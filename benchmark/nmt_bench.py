"""seq2seq NMT tokens/sec benchmark — the book/08 machine-translation
model WITH attention, trained end-to-end.

reference harness shape: benchmark/paddle/rnn/rnn.py (throughput over a
fixed synthetic batch); model: the seqToseq attention network of
book/08.machine_translation (v2 demo/seqToseq — bidirectional GRU
encoder, Bahdanau attention via networks.simple_attention, GRU-style
decoder driven per step by recurrent_group/DynamicRNN).

Metric: TARGET tokens/sec through a full train step (fwd+bwd+update) —
the standard NMT throughput convention.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.lod import build_lod_tensor


def build_model(dict_size, word_dim, hidden):
    from paddle_tpu import trainer_config_helpers as tch
    from paddle_tpu.trainer_config_helpers import networks as N

    src = tch.data_layer("src", size=dict_size, dtype="int64",
                         is_seq=True)
    src_emb = tch.embedding_layer(input=src, size=word_dim)
    enc = N.bidirectional_gru(input=tch.fc_layer(src_emb, size=hidden * 3),
                              size=hidden, return_seq=True)
    enc_proj = tch.fc_layer(enc, size=hidden)
    boot = tch.fc_layer(tch.last_seq(enc), size=hidden,
                        act=tch.TanhActivation())
    trg = tch.data_layer("trg", size=dict_size, dtype="int64",
                         is_seq=True)
    trg_emb = tch.embedding_layer(input=trg, size=word_dim)

    def step(cur_word, enc_seq, enc_p):
        s_pre = tch.memory("s", size=hidden, boot_layer=boot)
        ctx = N.simple_attention(encoded_sequence=enc_seq,
                                 encoded_proj=enc_p,
                                 decoder_state=s_pre)
        s = tch.fc_layer([cur_word, ctx, s_pre], size=hidden,
                         act=tch.TanhActivation(), name="s")
        return tch.fc_layer(s, size=dict_size,
                            act=tch.SoftmaxActivation())

    out = tch.recurrent_group(step, input=[
        trg_emb,
        tch.StaticInput(enc, is_seq=True),
        tch.StaticInput(enc_proj, is_seq=True)])
    lbl = tch.data_layer("lbl", size=dict_size, dtype="int64",
                         is_seq=True)
    cost = tch.classification_cost(input=out, label=lbl)
    return cost.var


def bench(batch_size=64, src_len=30, trg_len=30, dict_size=30000,
          word_dim=512, hidden=512, iters=6, warmup=2):
    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    from paddle_tpu.core import unique_name
    unique_name._counters.clear()
    with unique_name.guard():
        cost = build_model(dict_size, word_dim, hidden)
        pt.Adam(learning_rate=5e-4).minimize(cost)

    exe = pt.Executor(pt.TPUPlace())
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)

    def ragged(length, n):
        return build_lod_tensor(
            [rng.randint(1, dict_size, (length, 1)).astype("int64")
             for _ in range(n)])

    trg = ragged(trg_len, batch_size)
    feed = {"src": ragged(src_len, batch_size), "trg": trg, "lbl": trg}
    if hasattr(exe, "prepare_feed"):
        feed = exe.prepare_feed(feed)
    for _ in range(max(warmup, 1)):
        out, = exe.run(feed=feed, fetch_list=[cost], return_numpy=False)
    np.asarray(out)  # sync: the warm-up has finished on the device
    best = float("inf")
    for _ in range(3):  # best-of-3 windows
        t0 = time.perf_counter()
        for _ in range(iters):
            out, = exe.run(feed=feed, fetch_list=[cost],
                           return_numpy=False)
        np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    tgt_tokens = batch_size * trg_len
    return {"model": "nmt_attention_h%d" % hidden,
            "batch_size": batch_size, "src_len": src_len,
            "trg_len": trg_len, "dict_size": dict_size,
            "ms_per_batch": round(best * 1e3, 2),
            "tokens_per_sec": round(tgt_tokens / best, 2)}


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--src_len", type=int, default=30)
    p.add_argument("--trg_len", type=int, default=30)
    p.add_argument("--dict_size", type=int, default=30000)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--word_dim", type=int, default=512)
    p.add_argument("--iters", type=int, default=6)
    args = p.parse_args()
    print(json.dumps(bench(args.batch_size, args.src_len, args.trg_len,
                           args.dict_size, args.word_dim, args.hidden,
                           iters=args.iters)))
