"""Layer ``kernels`` (decode attention read + weight stream): the least time
for the window's decode steps, per step (weight bytes + K and V bytes of the
LIVE tokens of the running rows, counted at each launch) / peak bandwidth,
over the device time of the decode program. The count is of the work,
whatever implements it (gather or Pallas kernel). Bound by bytes: a decode
step multiplies each weight with at most 32 rows. Moves serve_tpot_p95_ms."""


def read(ctx):
    decode = (ctx.get("programs") or {}).get("decode")
    if not decode or decode["seconds"] <= 0:
        return None
    c, f = ctx["config"], ctx["flops"]
    weight_bytes = 4 * f.transformer_matmul_params(
        c["hidden_size"], c["num_hidden_layers"], c["ffn_dim"],
        c["vocab_size"])
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    floor_s = (decode["count"] * f.decode_step_floor_s(
        weight_bytes, 0, c["num_hidden_layers"], c["hidden_size"], 4, bw)
        + f.decode_step_floor_s(0, decode["live"], c["num_hidden_layers"],
                                c["hidden_size"], 4, bw))
    return 100.0 * floor_s / decode["seconds"]
