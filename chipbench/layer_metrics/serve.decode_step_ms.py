"""Layer ``engine`` (serving/generator.py): device time of the decode
program per decode step of the traced window. Moves serve_tpot_p95_ms."""


def read(ctx):
    decode = (ctx.get("programs") or {}).get("decode")
    if not decode or not decode["count"]:
        return None
    return decode["seconds"] / decode["count"] * 1e3
