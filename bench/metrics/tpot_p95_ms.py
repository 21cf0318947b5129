"""95th percentile gap between consecutive output tokens of a request,
over every such gap inside the window, of every request.

Each token is stamped on the host when the step that made it returns
(the step ends in a synchronous copy of its tokens to the host), so a gap
is what a client streaming the tokens would see."""
from harness.stats import percentile


def read(ctx):
    gaps = []
    for r in ctx.window.records:
        t = [x for x in r.token_s if x < ctx.seconds]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    p = percentile(gaps, 95)
    return None if p is None else p * 1e3
