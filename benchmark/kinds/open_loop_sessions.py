"""Traffic kind ``open_loop_sessions``: chat sessions arriving on a fixed
schedule (``loadgen.open_loop_sessions``), whatever the server does."""
from benchmark.kinds import serve


def run(cell, ctx) -> dict:
    return serve.run(cell, ctx, closed=False)
