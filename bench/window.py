"""The system under test and the loop that drives it.

``deploy`` makes what a configuration deploys (``Server``): the serving
engine that the configuration's model family builds
(``bench/families/<family>.py``), or, under ``"replicas": N``, N such
engines on N devices behind the program's ``ReplicaRouter``; ``warm_up``
runs each program the window will run once, on every engine.  ``drive``
owns the clock.  One engine: a request is admitted into a free slot as soon
as it is due, the engine steps while any slot is active, and the loop
sleeps until the next due time when none is.  Replicas: see
``_drive_router``.  A request's latency runs from its due time until
``step`` returns it with its output on the host.  Python's collector is
frozen and off while the window runs, so no collection lands inside it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import heapq
import time
from typing import (Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import Request

# after the window closes, requests still due or in flight are waited for
# this long before they count as failed
DRAIN_S = 60.0
# the device state of a serving engine, placed with its replica
ENGINE_ARRAYS = ("state", "x", "plan", "acc", "slot_acc", "metrics")


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


@dataclasses.dataclass
class Server:
    """What a configuration deploys: its engines, the device each engine's
    weights and state live on (None: the default device, one engine), the
    family's ``to_engine``, and, over several engines, the router."""
    engines: List
    devices: List
    to_engine: Callable
    router: Optional[object] = None

    def on(self, i: int):
        """The context in which engine ``i`` is called: its device is the
        default one, so what the engine makes lands there."""
        dev = self.devices[i]
        return (contextlib.nullcontext() if dev is None
                else jax.default_device(dev))

    def place(self, params) -> None:
        """Serve ``params`` (of the shapes built for) on every engine."""
        for i, eng in enumerate(self.engines):
            dev = self.devices[i]
            eng.params = params if dev is None else jax.device_put(params,
                                                                   dev)

    def commit(self, i: int) -> None:
        """Commit engine ``i``'s device state to its device.  The engine
        makes it there but uncommitted, and a program that reads it
        uncommitted beside committed weights is another program: one the
        warm-up did not run."""
        if self.devices[i] is not None:
            eng = self.engines[i]
            for name in ENGINE_ARRAYS:
                setattr(eng, name, jax.device_put(getattr(eng, name),
                                                  self.devices[i]))

    def reset_clock(self) -> None:
        for i, eng in enumerate(self.engines):
            eng.reset_clock()
            self.commit(i)

    def block(self) -> None:
        for eng in self.engines:
            jax.block_until_ready(eng.x)


def deploy(fam, cfg: Dict, params, max_steps: int,
           engine_hook: Optional[Callable] = None) -> Server:
    """The engines of configuration ``cfg`` over ``params``, made by the
    model family ``fam``, every program they run in the window run once.
    With ``"replicas": N``, one engine on each of the first N devices, its
    own copy of the weights committed there, behind ``ReplicaRouter`` with
    the router's own dispatch (join-shortest-queue on outstanding steps, no
    affinity) and each replica an ``SLOScheduler`` that sheds nothing (no
    degradation controller; requests carry no deadline).  ``engine_hook``
    sees each engine before its warm-up (tests break the timed path)."""
    n = int(cfg.get("replicas", 1))
    devices = [None] if n == 1 else jax.devices()[:n]
    if len(devices) < n:
        raise ValueError(f"{n} replicas need {n} devices; JAX sees "
                         f"{len(devices)}")
    srv = Server([], devices, fam.to_engine)
    for i, dev in enumerate(devices):
        with srv.on(i):
            eng, warm = fam.build(cfg, params if dev is None
                                  else jax.device_put(params, dev),
                                  max_steps)
            srv.engines.append(eng)
            srv.commit(i)
            if engine_hook is not None:
                engine_hook(eng)
            warm_up(eng, warm, fam.to_engine)
            srv.commit(i)
    if n > 1:
        from repro.serving import ReplicaRouter, SLOScheduler
        srv.router = ReplicaRouter([SLOScheduler(e) for e in srv.engines])
    return srv


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


def warm_up(eng, warm: Sequence[Request], to_engine: Callable) -> None:
    """Run every program the window runs once: the cold first step, a
    mid-flight admission (the mixed warm/cold step), the all-warm gated
    step, completion with its harvest and slot reset, and the slot copy
    that the check takes, with the family's two requests (``a`` one step
    longer than ``b``).  Step budgets and guidance scales are data to
    these programs, so any values reach them.  Leaves the engine idle with
    its clocks rewound."""
    a, b = warm
    eng.add_request(to_engine(a, eng.clock))
    eng.step()                                        # all rows cold
    _settle([({0: tap(eng, 0)}, 0)], wait=True)
    eng.add_request(to_engine(b, eng.clock))
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
    replicas: List[int] = dataclasses.field(
        default_factory=list)         # admissions by each replica (router)


def drive(srv: Server, traffic: Union[List[Request], Iterator[Request]],
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
        if srv.router is not None:
            return _drive_router(srv, traffic, seconds, counter,
                                 backlog_depth, drain_s, annotate, on_tick,
                                 watch or {})
        return _drive(srv.engines[0], srv.to_engine, traffic, seconds,
                      counter, backlog_depth, drain_s, annotate, on_tick,
                      watch or {})
    finally:
        gc.enable()
        gc.unfreeze()


def _drive(eng, to_engine, traffic, seconds, counter, backlog_depth,
           drain_s, annotate, on_tick, watch) -> WindowResult:
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
                    eng.add_request(to_engine(r, eng.clock))
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


def _drive_router(srv, traffic, seconds, counter, backlog_depth, drain_s,
                  annotate, on_tick, watch) -> WindowResult:
    """``_drive`` for replicas behind the router: a due request goes to
    ``ReplicaRouter.dispatch``, and while any replica holds or queues a
    request each loop turn ticks every replica in order (``SLOScheduler.
    tick``: its admission, then one engine step), as ``ReplicaRouter.run``
    does.  A request admitted in a tick counts as admitted when the tick
    began."""
    span = annotate or (lambda name: contextlib.nullcontext())
    router, engines = srv.router, srv.engines
    open_loop = isinstance(traffic, list)
    pending = collections.deque(traffic if open_loop else ())
    attempted: List[Request] = []
    by_rid: Dict[int, Request] = {}
    held: List[Dict[int, Request]] = [{} for _ in engines]  # slot -> request
    admitted = [0] * len(engines)
    clock = time.perf_counter
    compiles0 = counter.compiles
    busy, active_since = 0.0, None
    closed = None
    moving: List = []
    stalls: List[Tuple[float, float, str]] = []
    what = ["start"]

    def queued() -> int:
        return sum(len(q) for q in router.queues)

    def dispatch(r: Request) -> None:
        by_rid[r.rid] = r          # the replicas' clocks run in lockstep
        router.dispatch(srv.to_engine(r, engines[0].clock))

    def admit(r: Request, i: int, s: int, at: float) -> None:
        nonlocal active_since
        r.admit_t, r.slot, r.replica = at, s, i
        admitted[i] += 1
        if closed is None:
            attempted.append(r)
        if active_since is None:
            active_since = at

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
                srv.block()
                close_s = clock() - t0
                acc: Dict[str, float] = {}
                in_flight, steps_done = {}, {}
                for eng, slots in zip(engines, held):
                    for k, v in eng.acc.items():
                        acc[k] = acc.get(k, 0.0) + float(v)
                    rows = {k: np.asarray(v) for k, v in eng.slot_acc.items()}
                    for s, r in slots.items():
                        in_flight[r.rid] = {k: float(v[s])
                                            for k, v in rows.items()}
                        steps_done[r.rid] = int(eng.slot_step[s])
            if active_since is not None:
                busy += close_s - active_since
                active_since = close_s
            closed = WindowResult(
                requests=attempted, seconds=seconds, close_s=close_s,
                busy_s=busy, model_steps=sum(e.model_steps for e in engines),
                acc=acc, in_flight=in_flight, steps_done=steps_done,
                compiles=counter.compiles - compiles0, drained_s=close_s)
            if not open_loop:                 # the backlog stops waiting
                for q, e in zip(router.queues, engines):
                    while q.pop_arrived(e.clock) is not None:
                        pass
        active = any(held)
        if closed is not None and not (pending or queued() or active):
            break
        if closed is not None and now >= seconds + drain_s:
            break
        refill = closed is None and not open_loop
        if (pending and pending[0].due <= now) or (
                refill and queued() < backlog_depth):
            what.append("dispatch")
            with span("dispatch"):
                while pending and pending[0].due <= now:
                    dispatch(pending.popleft())
                while refill and queued() < backlog_depth:
                    r = next(traffic)
                    r.due = now
                    dispatch(r)
        if active or queued():
            for i, (sched, queue) in enumerate(zip(router.scheds,
                                                   router.queues)):
                eng = sched.engine
                ends = any(eng.slot_step[s] + 1 >= eng.slot_budget[s]
                           for s in held[i])
                what.append("harvest" if ends else "step")
                with srv.on(i), span(what[-1]):
                    began = clock() - t0
                    finished = sched.tick(queue)
                    t = clock() - t0
                    for s, dr in enumerate(eng.slots):
                        if dr is not None and s not in held[i]:
                            held[i][s] = by_rid[dr.rid]
                            admit(held[i][s], i, s, began)
                    for s, r in held[i].items():
                        j = int(eng.slot_step[s])
                        if j in watch.get(r.rid, ()):
                            r.taps[j] = tap(eng, s)
                            moving.append((r.taps, j))
                for fr in finished:
                    r = by_rid[fr.rid]
                    if r.admit_t is None:     # admitted and ended in a tick
                        admit(r, i, -1, began)
                    r.done_t, r.latents, r.cache = t, fr.latents, fr.cache
                    held[i].pop(r.slot, None)
            moving = _settle(moving)
            if not any(held) and active_since is not None:
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
    closed.replicas = admitted
    _settle(moving, wait=True)
    if open_loop:
        closed.requests = list(traffic)       # every request due in it
    return closed
