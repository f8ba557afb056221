"""Device time by the program's named scopes.

The serving program names the parts of its step with ``jax.named_scope``:
the DiT block's ``adaln``, ``attention`` and ``mlp``, FastCache's stages
(``fastcache.partition``, ``.bypass``, ``.gate``, ``.block``,
``.payload``, ``.full_forward``), ``merge`` and ``unmerge``, and the
sampler's phases.
A profiler trace names a device op by its HLO instruction alone, so an
op's scope is read from the serve step's compiled text, where each
instruction carries the path it was traced under as its ``op_name``
metadata.  A fusion takes the op_name that the compiler gave the fusion
instruction (for an output fusion, that of its matmul), else that of its
fused computation's root, else (a root the compiler left unnamed, such as
a scatter) that of the computation's last named instruction.

``serve_step_text`` compiles the step of the engine that the cell's model
family builds (``build`` of ``bench/families/<family>.py``), from shapes
alone; with the persistent compilation cache on, this reads the run's own
executable back.  An op of the engine's other programs
(admission, slot reset, slot copy) that shares its instruction name with an
op of the step is counted as the step's op.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Tuple

SCOPES = ("adaln", "attention", "mlp", "fastcache.partition",
          "fastcache.bypass", "fastcache.gate", "fastcache.block",
          "fastcache.payload", "fastcache.full_forward", "merge", "unmerge",
          "cfg_double", "model_eval", "cfg_blend", "ddim_update")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,}]+)")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name, over a compiled module's text."""
    named: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    last: Dict[str, str] = {}           # computation -> last named op
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                comp = head.group(1)
            continue
        name = m.group(2)
        o = _OP_NAME.search(line)
        if o is not None:
            named[name] = o.group(1)
            last[comp] = name
        c = _CALLS.search(line)
        if c is not None:
            calls[name] = c.group(1)
        if m.group(1):
            roots[comp] = name

    def of_computation(comp: str, seen: frozenset) -> Optional[str]:
        root = roots.get(comp)
        if root in named:
            return named[root]
        if root in calls and calls[root] not in seen:
            inner = of_computation(calls[root], seen | {calls[root]})
            if inner is not None:
                return inner
        return named.get(last.get(comp, ""))

    out = dict(named)
    for name, comp in calls.items():
        if name not in out:
            op = of_computation(comp, frozenset({comp}))
            if op is not None:
                out[name] = op
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on ``op_name``'s path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def seconds_by_scope(top_ops: Iterable[Tuple[str, float]],
                     names: Dict[str, str]) -> Dict[str, float]:
    """Device seconds by innermost scope; "other" for ops in none."""
    out: Dict[str, float] = {}
    for op, secs in top_ops:
        key = scope_of(names.get(op, "")) or "other"
        out[key] = out.get(key, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def seconds_under(top_ops: Iterable[Tuple[str, float]],
                  names: Dict[str, str], scope: str) -> float:
    """Device seconds of the ops traced anywhere under ``scope``."""
    return sum(secs for op, secs in top_ops
               if scope in names.get(op, "").split("/"))


def serve_step_text(cell) -> str:
    """The compiled text of the serve step of ``cell``'s engine."""
    import jax
    import jax.numpy as jnp

    from bench import loadgen
    from bench.spec import family

    cfg = cell.config
    fam = family(cfg, cell.root)
    d = fam.dims_of(cfg)
    params = jax.eval_shape(lambda: fam.make_params(d, 0, cfg["dtype"]))
    eng, _ = fam.build(cfg, params, loadgen.max_steps(cell.mix))

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), tree)

    slot = jax.ShapeDtypeStruct((eng.S,), jnp.int32)
    args = (params, *shapes((eng.state, eng.x, eng.plan)), slot, slot,
            jax.ShapeDtypeStruct((eng.S,), jnp.bool_),
            *shapes((eng.acc, eng.slot_acc, eng.metrics)),
            jax.ShapeDtypeStruct((), jnp.bool_))
    return eng._step.lower(*args).compile().as_text()
