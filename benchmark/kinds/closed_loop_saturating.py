"""Traffic kind ``closed_loop_saturating``: a fixed number of clients, each
sending its next distinct prompt when the last one finished
(``loadgen.closed_loop_prompts``)."""
from benchmark.kinds import serve


def run(cell, ctx) -> dict:
    return serve.run(cell, ctx, closed=True)
