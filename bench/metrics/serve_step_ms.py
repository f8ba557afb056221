"""Wall time in which the engine held a request, up to the close (where
the device is waited for), over the serve steps in the window."""


def read(run):
    w = run.window
    return 1e3 * w.busy_s / w.model_steps if w.model_steps else None
