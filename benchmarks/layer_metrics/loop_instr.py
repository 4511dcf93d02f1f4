"""What the readers of the account's own cost share. The loop account
(`ceph_tpu/utils/loopprof.py`) books the instruments themselves as an
"of which": a `loop_slice` span's `instr` tag holds the microseconds of
the slice that were a span's life outside its body, a section's way in
and out, the callback hook's own lines (calibrated, not timed) and the
closing of slices (`by_kind`), the same microseconds by the part of the
account that had been charged them (`in_part`), and how many spans and
sections closed (`spans`, `sections`). No part loses them: the accepted
`*_ms_per_op` include their observer, and this says how much it is."""


def _tags(ctx):
    """The `instr` tags of the window's slices; empty on a parent whose
    account does not book itself."""
    return [s["tags"]["instr"] for s in ctx.spans.get("loop_slice", [])
            if "instr" in s["tags"]]


def ms_per_op(ctx, key, parts=None):
    """`instr[key]` (`by_kind` or `in_part`) summed over the window's
    slices, over `parts` of it where given, / ops completed / 1000; None
    where no slice carries `instr` or no op completed."""
    tags = _tags(ctx)
    if not ctx.ops or not tags:
        return None
    return sum(v for t in tags for k, v in t[key].items()
               if parts is None or k in parts) / ctx.ops / 1000.0


def count_per_op(ctx, key):
    """`instr[key]` (`spans` or `sections`) summed / ops completed."""
    tags = _tags(ctx)
    if not ctx.ops or not tags:
        return None
    return sum(t[key] for t in tags) / ctx.ops
