"""The system under test and the loop that drives it.

``build`` makes the serving engine of a configuration exactly as the
serving launcher does (``repro.launch.serve_diffusion``): a ``CachedDiT``
runner under the configured cache policy inside a
``DiffusionServingEngine`` with ``slots`` slots.  ``warm_up`` runs each
program the window will run once.  ``drive`` owns the clock: a request is
admitted into a free slot as soon as it is due, the engine steps while any
slot is active, and the loop sleeps until the next due time when none is.
A request's latency runs from its due time until ``step`` returns it with
its latents on the host.  Python's collector is frozen and off while the
window runs, so no collection lands inside it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import heapq
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import Request

# after the window closes, requests still due or in flight are waited for
# this long before they count as failed
DRAIN_S = 60.0


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring``."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def model_config(cfg: Dict):
    """The program's model configuration for a configuration file."""
    from repro.configs import get_config
    from repro.configs.base import DiTConfig
    hidden = int(cfg["hidden_size"])
    return get_config(cfg["model"]).replace(
        num_layers=int(cfg["depth"]), d_model=hidden,
        num_heads=int(cfg["num_heads"]), num_kv_heads=int(cfg["num_heads"]),
        d_ff=int(round(cfg["mlp_ratio"] * hidden)), dtype=cfg["dtype"],
        dit=DiTConfig(patch_size=int(cfg["patch_size"]),
                      in_channels=int(cfg["in_channels"]),
                      num_classes=int(cfg["num_classes"]),
                      learn_sigma=bool(cfg["learn_sigma"]),
                      image_size=int(cfg["input_size"])))


def build(cfg: Dict, params, max_steps: int):
    """The serving engine of configuration ``cfg`` over ``params``."""
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.models import build_model
    from repro.serving import DiffusionServingEngine

    model = build_model(model_config(cfg))
    runner = CachedDiT(model, FastCacheConfig(**cfg.get("fastcache", {})),
                       policy=cfg["policy"])
    return DiffusionServingEngine(runner, params, max_slots=int(cfg["slots"]),
                                  num_steps=max_steps, max_steps=max_steps)


def tap(eng, s: int):
    """A copy of slot ``s``'s latents and cache state (the engine's own slot
    snapshot), already on its way to the host."""
    snap = eng._snapshot(eng.state, eng.x, eng.plan, eng.slot_acc,
                         eng._slot_rows(s), jnp.asarray(s, jnp.int32))
    for leaf in jax.tree.leaves(snap):
        leaf.copy_to_host_async()
    return snap


def _settle(moving: List, wait: bool = False) -> List:
    """Host copies of the taps that the device has finished; the rest."""
    rest = []
    for taps, j in moving:
        if wait or all(v.is_ready() for v in jax.tree.leaves(taps[j])):
            taps[j] = jax.tree.map(np.asarray, taps[j])
        else:
            rest.append((taps, j))
    return rest


def _to_engine(r: Request, clock: int):
    from repro.serving import DiffusionRequest
    return DiffusionRequest(rid=r.rid, label=r.label, seed=r.noise_seed,
                            arrival_step=clock, num_steps=r.steps,
                            guidance_scale=r.guidance)


def warm_up(eng) -> None:
    """Run every program the window runs once: the cold first step, a
    mid-flight admission (the mixed warm/cold step), the all-warm gated
    step, completion with its harvest and slot reset, and the slot copy
    that the check takes.  Step budgets and guidance scales are data to
    these programs, so any values reach them.  Leaves the engine idle with
    its clocks rewound."""
    a = Request(rid=-1, label=0, steps=3, guidance=4.0, noise_seed=1)
    b = Request(rid=-2, label=1, steps=2, guidance=1.0, noise_seed=2)
    eng.add_request(_to_engine(a, eng.clock))
    eng.step()                                        # all rows cold
    _settle([({0: tap(eng, 0)}, 0)], wait=True)
    eng.add_request(_to_engine(b, eng.clock))
    eng.step()                                        # warm + cold rows
    done = eng.step()                                 # all warm; both end
    if len(done) != 2:
        raise RuntimeError(f"warm-up finished {len(done)} of 2 requests")
    jax.block_until_ready(eng.x)
    eng.reset_clock()


@dataclasses.dataclass
class WindowResult:
    requests: List[Request]           # every request attempted
    seconds: float                    # the window as asked
    close_s: float                    # when the device had caught up
    busy_s: float                     # engine active, up to the close
    model_steps: int                  # serve steps dispatched in the window
    acc: Dict[str, float]             # engine.acc at the close
    in_flight: Dict[int, Dict]        # rid -> slot_acc row at the close
    steps_done: Dict[int, int]        # rid -> its steps at the close
    compiles: int                     # compiles inside the window
    drained_s: float                  # when the last request ended
    stalls: List[Tuple[float, float, str]] = dataclasses.field(
        default_factory=list)         # longest loop turns: (s, at, span)


def drive(eng, traffic: Union[List[Request], Iterator[Request]],
          seconds: float, counter: CompileCounter, *,
          backlog_depth: int = 0, drain_s: float = DRAIN_S,
          annotate: Optional[Callable[[str], object]] = None,
          on_tick: Optional[Callable[[float], None]] = None,
          watch: Optional[Dict[int, Tuple[int, ...]]] = None
          ) -> WindowResult:
    """Serve ``traffic`` for ``seconds``.  A list is open-loop (each
    request's ``due``); an iterator is a closed backlog that keeps
    ``backlog_depth`` requests waiting while the window is open.  ``watch``
    maps a rid to the steps after which its slot is copied (``tap``)."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return _drive(eng, traffic, seconds, counter, backlog_depth,
                      drain_s, annotate, on_tick, watch or {})
    finally:
        gc.enable()
        gc.unfreeze()


def _drive(eng, traffic, seconds, counter, backlog_depth, drain_s,
           annotate, on_tick, watch) -> WindowResult:
    span = annotate or (lambda name: contextlib.nullcontext())
    open_loop = isinstance(traffic, list)
    pending = collections.deque(traffic if open_loop else ())
    waiting: collections.deque = collections.deque()
    attempted: List[Request] = []
    by_rid: Dict[int, Request] = {}
    slot_req: Dict[int, Request] = {}
    clock = time.perf_counter
    compiles0 = counter.compiles
    busy, active_since = 0.0, None
    closed = None
    moving: List = []
    stalls: List[Tuple[float, float, str]] = []
    what = ["start"]
    t0 = clock()
    last = 0.0
    while True:
        now = clock() - t0
        heapq.heappush(stalls, (now - last, last, "+".join(what)))
        if len(stalls) > 5:
            heapq.heappop(stalls)
        last, what = now, []
        if on_tick is not None:
            on_tick(now)
        if closed is None and now >= seconds:
            what.append("close")
            with span("close"):
                jax.block_until_ready(eng.x)
                close_s = clock() - t0
                acc = {k: float(v) for k, v in eng.acc.items()}
                rows = {k: np.asarray(v) for k, v in eng.slot_acc.items()}
            if active_since is not None:
                busy += close_s - active_since
                active_since = close_s
            closed = WindowResult(
                requests=attempted, seconds=seconds, close_s=close_s,
                busy_s=busy, model_steps=eng.model_steps,
                acc=acc,
                in_flight={r.rid: {k: float(v[s]) for k, v in rows.items()}
                           for s, r in slot_req.items()},
                steps_done={r.rid: int(eng.slot_step[s])
                            for s, r in slot_req.items()},
                compiles=counter.compiles - compiles0, drained_s=close_s)
            if not open_loop:
                waiting.clear()
        active = bool(slot_req)
        if closed is not None and not (pending or waiting or active):
            break
        if closed is not None and now >= seconds + drain_s:
            break
        while pending and pending[0].due <= now:
            waiting.append(pending.popleft())
        if closed is None and not open_loop:
            while len(waiting) < backlog_depth:
                r = next(traffic)
                r.due = now
                waiting.append(r)
        free = eng.free_slots()
        if waiting and free:
            what.append("admit")
            with span("admit"):
                while waiting and free:
                    r = waiting.popleft()
                    s = free.pop(0)
                    eng.add_request(_to_engine(r, eng.clock))
                    r.admit_t, r.slot = clock() - t0, s
                    slot_req[s] = r
                    by_rid[r.rid] = r
                    if closed is None:
                        attempted.append(r)
            if active_since is None:
                active_since = slot_req[min(slot_req)].admit_t
        if slot_req:
            ends = any(eng.slot_step[s] + 1 >= eng.slot_budget[s]
                       for s in slot_req)
            what.append("harvest" if ends else "step")
            with span(what[-1]):
                finished = eng.step()
                t = clock() - t0
            for s, r in list(slot_req.items()):
                if int(eng.slot_step[s]) in watch.get(r.rid, ()):
                    r.taps[int(eng.slot_step[s])] = tap(eng, s)
                    moving.append((r.taps, int(eng.slot_step[s])))
            moving = _settle(moving)
            for fr in finished:
                r = by_rid[fr.rid]
                r.done_t, r.latents, r.cache = t, fr.latents, fr.cache
                del slot_req[r.slot]
            if not slot_req and active_since is not None:
                if closed is None:
                    busy += t - active_since
                active_since = None
        else:
            nxt = pending[0].due if pending else seconds + drain_s
            if closed is None:
                nxt = min(nxt, seconds)
            what.append("sleep")
            with span("sleep"):
                time.sleep(max(0.0, min(nxt - (clock() - t0), 0.05)))
    closed.drained_s = clock() - t0
    closed.stalls = sorted(stalls, reverse=True)
    _settle(moving, wait=True)
    if open_loop:
        closed.requests = list(traffic)       # every request due in it
    return closed
