"""Blocks that the device ran over active rows beyond those the rows' own
cache decisions computed, over all it ran, in %: (blocks_run -
blocks_computed) / blocks_run from the engine's on-device counters at the
close.  A gated block runs for every row unless every row caches it, and a
step after an admission or a completion runs a full forward on top of the
gated path: both are work thrown away.  None where the program does not
count ``blocks_run``."""


def read(run):
    acc = run.window.acc
    ran = acc.get("blocks_run")
    if not ran:
        return None
    return 100.0 * (ran - acc.get("blocks_computed", 0.0)) / ran
