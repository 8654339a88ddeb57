"""Layer ``engine``: device time of the prefill programs over the prompt
kilo-tokens prefilled in the traced window. Moves serve_ttft_p95_ms."""


def read(ctx):
    prefill = (ctx.get("programs") or {}).get("prefill")
    if not prefill or not prefill["size"]:
        return None
    return prefill["seconds"] * 1e3 / (prefill["size"] / 1e3)
