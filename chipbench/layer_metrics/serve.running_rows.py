"""Layer ``engine``: rows in the decode batch, averaged over the decode
steps of the window (``stats()["running_occupancy"]``, a count). Moves
serve_tokens_per_s."""


def read(ctx):
    b, a = ctx["stats_before"], ctx["stats_at_close"]
    steps = a["decode_steps"] - b["decode_steps"]
    if steps <= 0:
        return None
    return (a["running_occupancy"] * a["decode_steps"]
            - b["running_occupancy"] * b["decode_steps"]) / steps
