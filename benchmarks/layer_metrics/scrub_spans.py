"""What the `scrub_*` readers share: the window's `scrub_round` spans
(one a round, on its primary: `pgid`, `deep`, `state` done /
reserve_failed / aborted, `objects`, `bytes` and the legs `reserve_us`,
`grant_wait_us`, `scan_us`, `digest_us`, `compare_us`) and `scrub_chunk`
spans (one a scan chunk on every OSD that builds a map: `objects`,
`bytes`, `blocks`). A program that opens no such span, as the parent of
the PR that brought them, has nothing here and every reader built on
this returns None there."""


def rounds(ctx, state=None):
    """The window's rounds, or those that ended in `state`."""
    return [s for s in ctx.spans.get("scrub_round", [])
            if state is None or s["tags"].get("state") == state]
