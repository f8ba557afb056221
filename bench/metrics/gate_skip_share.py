"""Blocks the cache gate skipped over all it decided, active rows only,
from the engine's on-device counters at the close, in %."""


def read(run):
    acc = run.window.acc
    if "blocks_skipped" not in acc:
        return None
    total = acc["blocks_skipped"] + acc.get("blocks_computed", 0.0)
    return 100.0 * acc["blocks_skipped"] / total if total else None
