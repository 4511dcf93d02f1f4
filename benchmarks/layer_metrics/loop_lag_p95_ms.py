"""95th percentile of how late the account's 10 ms ticker ran, from the
`lag_hist` histograms of the window's `loop_slice` spans (`lag_edges_ms`
are the buckets' upper edges; linear inside a bucket). It is the delay
each of an op's hops pays for its turn on the loop."""
NAME = "loop_lag_p95_ms"
UNIT = "ms"
LAYER = "event loop (all daemons)"
MOVES = "op_p95_ms"


def read(ctx):
    slices = [s["tags"] for s in ctx.spans.get("loop_slice", [])
              if "lag_hist" in s["tags"]]
    if not slices:
        return None
    edges = [0.0, *slices[0]["lag_edges_ms"]]
    edges.append(2 * edges[-1])          # the open bucket, closed for p95
    hist = [sum(c) for c in zip(*(t["lag_hist"] for t in slices))]
    want, seen = 0.95 * sum(hist), 0
    for count, lo, hi in zip(hist, edges, edges[1:]):
        if count and seen + count >= want:
            return lo + (hi - lo) * (want - seen) / count
        seen += count
    return None
