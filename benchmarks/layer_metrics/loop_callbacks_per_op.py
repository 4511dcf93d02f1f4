"""Callbacks the loop ran an op: the `callbacks` tag of the `loop_slice`
spans (PR 24's) summed over the window / ops completed. Each pays the
loop's own machinery, and under the account its hook."""

NAME = "loop_callbacks_per_op"
UNIT = "callbacks/op"
LAYER = "event loop (all daemons)"
MOVES = "ops_s"


def read(ctx):
    slices = [s["tags"] for s in ctx.spans.get("loop_slice", [])
              if "callbacks" in s["tags"]]
    if not ctx.ops or not slices:
        return None
    return sum(t["callbacks"] for t in slices) / ctx.ops
