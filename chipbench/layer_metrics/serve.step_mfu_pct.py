"""Layer ``model step`` (models/transformer.py): the whole step's share of
the chip's bf16 peak: 2 x matmul parameters x tokens processed (prompt +
output) per second of the traced window. Moves serve_tokens_per_s."""


def read(ctx):
    b, a, c = ctx["stats_before"], ctx["stats_at_close"], ctx["config"]
    tokens = (a["tokens_generated"] - b["tokens_generated"]
              + a["prompt_tokens"] - b["prompt_tokens"])
    if tokens <= 0 or not ctx.get("window_s"):
        return None
    per_token = ctx["flops"].transformer_token_flops(
        c["hidden_size"], c["num_hidden_layers"], c["ffn_dim"],
        c["vocab_size"])
    return (100.0 * per_token * tokens / ctx["window_s"]
            / ctx["peaks"]["flops_bf16"])
