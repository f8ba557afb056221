"""Device time of the ops traced under the DiT block's ``attention`` scope
(q/k/v, attention, output projection, gated residual) over the device's
busy time in the traced window, in %.  Ops are put down to scopes through
the compiled serve step (``bench/scopes.py``), whose split over every scope
is logged on stderr.  None without a trace or where no op of the step is
under the scope."""
import sys

from bench import scopes


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    names = scopes.op_names(scopes.serve_step_text(run.cell))
    split = scopes.seconds_by_scope(t.top_ops, names)
    print("[bench] device seconds by scope: " + ", ".join(
        f"{k} {v:.4f}" for k, v in split.items()), file=sys.stderr,
        flush=True)
    secs = scopes.seconds_under(t.top_ops, names, "attention")
    return 100.0 * secs / t.busy_s if secs > 0 else None
