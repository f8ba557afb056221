"""Images served per second of the window (host clock): every request
whose latents reached the host by the close, and for each request still in
a slot at the close the share of its steps that had run, over the window up
to the close.  Counting whole requests alone would move in steps of a
batch: a backlog's slots finish together, four images a batch."""


def read(run):
    w = run.window
    done = sum(1 for r in w.requests if r.rid not in w.in_flight
               and r.done_t is not None and r.done_t <= w.close_s)
    part = sum(w.steps_done[r.rid] / r.steps for r in w.requests
               if r.rid in w.in_flight)
    return (done + part) / w.close_s
