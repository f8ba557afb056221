"""From the profiler's trace to per-layer numbers.

``Recorder`` traces a span of the window (``jax.profiler``) and names the
benchmark's own host work with ``TraceAnnotation`` spans: ``admit``,
``step``, ``harvest`` (a step in which a request ends, with its blocking
fetch of the latents), ``sleep`` and ``close``.

``reduce`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
returns, over the traced window (the first to the last benchmark span):

  busy_s      the union of the intervals in which an XLA op ran on the
              device, averaged over the devices traced;
  kernels     calls and device seconds of each serving-path kernel, found
              by the name of its custom call, which is the name of the
              kernel's jitted wrapper in ``kernels/`` (``KERNELS``);
  top_ops     device seconds by HLO op, leaf ops only (a ``while`` or
              ``conditional`` op spans the ops it runs);
  idle        device-idle seconds by the benchmark span the host was in
              ("other" where it was in none).
"""
from __future__ import annotations

import dataclasses
import re
import shutil
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
# the TPU trace names an op by its HLO text, "%<name>[.<n>] = <shape> <op>("
KERNELS = ("fused_gate", "knn_density", "merge_assign", "unmerge_scatter")
_OP = re.compile(r"^%?(([A-Za-z_][A-Za-z0-9_\-]*)(?:\.[A-Za-z0-9_]+)*) = "
                 r"(.*?)\b([a-z][a-z\-]*)\(")
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernels: Dict[str, Tuple[int, float]]
    top_ops: List[Tuple[str, float]]
    idle: List[Tuple[str, float]]

    def breakdown(self) -> Dict[str, List]:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle[:10]]}


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def op_name(text: str) -> Tuple[str, str, str]:
    """(name, name without its instance suffixes, HLO opcode) of an op's
    text."""
    m = _OP.match(text)
    if m is None:
        return text.split(" ")[0], text.split(" ")[0], ""
    return m.group(1), m.group(2), m.group(4)


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path) -> Tuple[List[Tuple[int, int, str]],
                        List[List[Tuple[int, int, str]]]]:
    """The benchmark's host spans (start, end, name) and, per device, its
    XLA ops (start, end, HLO text), in nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans: List[Tuple[int, int, str]] = []
    devices: List[List[Tuple[int, int, str]]] = []
    for plane in pd.planes:
        if _is_device(plane.name):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name) for ev in ln.events])
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      ev.name[len(SPAN_PREFIX):]))
    return spans, devices


def summarize(spans, devices, kernels=KERNELS) -> Optional[Summary]:
    if not spans or not devices:
        return None
    spans = sorted(spans)
    lo = spans[0][0]
    # the window ends where the benchmark closed it
    hi = min([s[1] for s in spans if s[2] == "close"]
             or [max(s[1] for s in spans)])
    spans = [s for s in spans if s[0] < hi]
    busy = 0.0
    k_stats = {k: [0, 0.0] for k in kernels}
    by_name: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for ops in devices:
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        merged = _clip(_union((a, b) for a, b, _ in inside), lo, hi)
        busy += sum(b - a for a, b in merged) * 1e-9
        for a, b, text in inside:
            a, b = max(a, lo), min(b, hi)
            full, base, opcode = op_name(text)
            if opcode in _CONTAINERS:
                continue
            by_name[full] = by_name.get(full, 0.0) + (b - a) * 1e-9
            if base in k_stats:
                k_stats[base][0] += 1
                k_stats[base][1] += (b - a) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        j = 0                        # spans are sequential on one thread
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            while j < len(spans) and spans[j][1] <= a:
                j += 1
            left, k = b - a, j
            while k < len(spans) and spans[k][0] < b:
                over = min(b, spans[k][1]) - max(a, spans[k][0])
                if over > 0:
                    name = spans[k][2]
                    idle_by[name] = idle_by.get(name, 0.0) + over * 1e-9
                    left -= over
                k += 1
            if left > 0:
                idle_by["other"] = idle_by.get("other", 0.0) + left * 1e-9
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy / len(devices),
        kernels={k: (c, s) for k, (c, s) in k_stats.items() if c},
        top_ops=sorted(by_name.items(), key=lambda kv: -kv[1]),
        idle=sorted(idle_by.items(), key=lambda kv: -kv[1]))


def reduce(path, kernels=KERNELS) -> Optional[Summary]:
    return summarize(*load(path), kernels=kernels)


def start_trace(root: Path) -> None:
    """The profiler without its Python tracer, whose events would be most
    of the trace and most of its cost."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(root), profiler_options=opts)


class Recorder:
    """Traces into ``root`` from ``start_s`` of the window (ticked by the
    window's loop) until ``finish``, which stops the profiler (a stall of
    seconds, so only once the window has closed and drained), reduces the
    trace up to the close and deletes it."""

    def __init__(self, root: Path, start_s: float):
        self.root = Path(root)
        self.start_s = start_s
        self.state = 0                      # 0 before, 1 tracing, 2 done
        shutil.rmtree(self.root, ignore_errors=True)

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def tick(self, now: float) -> None:
        if self.state == 0 and now >= self.start_s:
            start_trace(self.root)
            self.state = 1

    def finish(self) -> Optional[Summary]:
        import jax
        if self.state == 1:
            jax.profiler.stop_trace()
            self.state = 2
        files = sorted(self.root.rglob("*.xplane.pb"))
        try:
            return reduce(files[-1]) if files else None
        finally:
            shutil.rmtree(self.root, ignore_errors=True)
