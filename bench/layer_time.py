"""Device time of the model's layers, joined to the programs' own HLO.

``generate`` runs the model step as two programs, ``jit_prefill`` and
``jit_decode`` on a trace's modules line.  The model names its layers with
``jax.named_scope`` (``embed``, ``attention``, ``experts``, ``mlp``,
``mixer``, ``head``), and every instruction a layer compiles to carries
the scope in its ``op_name``.  ``step_hlo`` lowers and compiles the two
programs as ``generate`` does for a traced batch's shape, which hands back
the executable that ran (the benchmark keeps JAX's compilation cache on),
and reads each instruction's ``op_name`` from it (``bench/hlo_scopes.py``).
On the TPU an operation's event is named by its instruction's HLO text
(``%fusion.138 = ...``), so each operation of a run is joined to its
instruction by name, and its time goes to the outermost layer scope on its
op path, or to ``other``.

Only leaf operations count: a ``while`` event covers its body's events.
Where more than ``MAX_UNMATCHED`` of a run's leaf time joins no
instruction of its program, the join has failed and nothing is returned.
The programs are compiled only where the trace shows runs of
``jit_prefill`` and ``jit_decode``.
"""
from __future__ import annotations

import dataclasses
import functools
import re

from bench import hlo_scopes, serve_trace, trace_reduce

SCOPES = ("embed", "attention", "experts", "mlp", "mixer", "head")
OTHER = "other"
PROGRAMS = {"prefill": "jit_prefill(", "decode": "jit_decode("}
MAX_UNMATCHED = 0.01
#: the instruction's name at the head of its HLO text
INSTRUCTION = re.compile(r"^(?:ROOT )?%?([^\s=]+)")
#: what ends a Pallas kernel's op path
KERNEL_OP = "pallas_call"


@dataclasses.dataclass
class Split:
    """Device seconds of some step runs, by layer scope."""
    #: {scope or "other": seconds of its leaf operations}
    total: dict[str, float]
    #: {scope: the part of ``total`` spent in Pallas kernels}
    kernel: dict[str, float]
    runs: int = 0
    #: leaf seconds that joined no instruction (under ``MAX_UNMATCHED``
    #: of each run's)
    unmatched: float = 0.0


def step_runs(trace, lo: float, hi: float, gen: int):
    """{"prefill": [run], "decode": [gen runs]} of the step programs on
    chip 0 in [lo, hi), by program name; None where they are not there
    (a program that names them otherwise)."""
    out = {k: [e for e in trace_reduce.executions(trace, lo, hi)
               if e.name.startswith(prefix)]
           for k, prefix in PROGRAMS.items()}
    if len(out["prefill"]) != 1 or len(out["decode"]) != gen:
        return None
    return out


def leaf_ops(trace, run) -> list:
    """The operations of ``run`` that hold no other operation."""
    ops = sorted(trace_reduce.ops_within(trace, run),
                 key=lambda o: (o.start, -o.dur))
    leaf = [True] * len(ops)
    stack: list[int] = []
    for i, op in enumerate(ops):
        while stack and ops[stack[-1]].end <= op.start:
            stack.pop()
        if stack and op.end <= ops[stack[-1]].end + 1e-9:
            leaf[stack[-1]] = False
        stack.append(i)
    return [op for op, is_leaf in zip(ops, leaf) if is_leaf]


def scope_of(op_name: str) -> str:
    return next((s for s in op_name.split("/") if s in SCOPES), OTHER)


def split(trace, runs, names: dict[str, str]) -> Split | None:
    """The leaf-op seconds of ``runs`` of one program, whose instructions'
    op paths are ``names``, by scope; None where a run's join fails."""
    out = Split({}, {}, len(runs))
    for run in runs:
        leaf_s = unmatched_s = 0.0
        for op in leaf_ops(trace, run):
            leaf_s += op.dur
            m = INSTRUCTION.match(op.name)
            op_name = names.get(m.group(1)) if m else None
            if op_name is None:
                unmatched_s += op.dur
                continue
            scope = scope_of(op_name)
            out.total[scope] = out.total.get(scope, 0.0) + op.dur
            if op_name.endswith(KERNEL_OP):
                out.kernel[scope] = out.kernel.get(scope, 0.0) + op.dur
        if unmatched_s > MAX_UNMATCHED * leaf_s:
            return None
        out.unmatched += unmatched_s
    return out


def add(a: Split, b: Split) -> Split:
    def merged(x, y):
        return {k: x.get(k, 0.0) + y.get(k, 0.0) for k in x.keys() | y}
    return Split(merged(a.total, b.total), merged(a.kernel, b.kernel),
                 a.runs + b.runs, a.unmatched + b.unmatched)


@functools.cache
def step_hlo(cfg, B: int, S: int, gen: int) -> dict[str, dict[str, str]]:
    """{"prefill": {instruction name: op_name}, "decode": {...}} of the
    programs ``generate`` compiles for a (B, S) batch decoding ``gen``
    tokens, lowered from the same shapes as there."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.model import lm

    params = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda p: lm.init_cache(p, cfg, B, max_seq=S + gen), params)
    out = {}
    for fn, T in zip(serve.step_programs(cfg), (S, 1)):
        compiled = fn.lower(params, cache, jax.ShapeDtypeStruct(
            (B, T), jnp.int32)).compile()
        module = compiled.runtime_executable().hlo_modules()[0]
        out[fn.__name__] = hlo_scopes.module_op_names(
            module.as_serialized_hlo_module_proto())
    return out


def traced_split(ctx, program: str) -> Split | None:
    """The split of every traced batch's ``program`` runs ("prefill" or
    "decode") together; None where a batch's runs cannot be told or
    joined."""
    from repro import configs

    total = None
    for b, (lo, hi) in serve_trace.traced_batches(ctx):
        found = step_runs(ctx.trace, lo, hi, b.gen)
        if found is None:
            return None
        cfg = configs.get(ctx.spec["program"]["arch"])
        s = split(ctx.trace, found[program],
                  step_hlo(cfg, b.B, b.S, b.gen)[program])
        if s is None:
            return None
        total = s if total is None else add(total, s)
    return total


def ms_per_run(ctx, program: str, scope: str, kernels: bool = True):
    """Mean ms a traced ``program`` run spends under ``scope``, without
    its Pallas kernels if not ``kernels``; None where the trace has no
    time under it."""
    s = None if ctx.trace is None else traced_split(ctx, program)
    if s is None or scope not in s.total:
        return None
    secs = s.total[scope] - (0.0 if kernels else s.kernel.get(scope, 0.0))
    return 1e3 * secs / s.runs
