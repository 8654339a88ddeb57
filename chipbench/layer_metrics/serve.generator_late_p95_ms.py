"""Layer ``load generator`` (the benchmark's own): 95th percentile of how
late each send ran against its schedule, so that a starved generator is not
read as a fast server. Moves serve_ttft_p95_ms."""


def read(ctx):
    return ctx.get("generator_late_p95_ms")
