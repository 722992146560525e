"""The serve step names its layers and its programs.

``generate`` compiles ``lm.step`` as two programs, ``jit_prefill`` and
``jit_decode``; inside them every matmul of the layer loop lies under the
``attention`` or ``experts`` scope of its layer, which is how a device
trace's operations are put under the model's layers
(``bench/layer_time.py``).  Compiled for the CPU at a reduced config;
``tests/test_tpu_compile.py`` checks the kernels' names at full width for
the chip.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch import serve
from repro.model import lm

B, S, GEN = 2, 8, 4
#: an instruction line of HLO text: name, opcode, and the rest
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?\s([a-z][\w-]*)\(")
COMPUTE = ("dot", "convolution")


def _computations(text: str) -> dict[str, list[str]]:
    """{computation name: its instruction lines} of an HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([^\s(]+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif name and INSTRUCTION.match(line):
            comps[name].append(line)
    return comps


def _holds_compute(comps, line) -> bool:
    """A dot or convolution, or a fusion whose computation holds one."""
    op = INSTRUCTION.match(line).group(2)
    if op in COMPUTE:
        return True
    called = re.search(r"calls=%([\w.\-]+)", line)
    return op == "fusion" and called is not None and any(
        _holds_compute(comps, inner) for inner in comps[called.group(1)])


@pytest.fixture(scope="module")
def compiled():
    cfg = configs.get_reduced("granite-moe-3b-a800m")
    params = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda p: lm.init_cache(p, cfg, B, max_seq=S + GEN), params)
    prefill, decode = serve.step_programs(cfg)
    return {fn.__name__: fn.lower(params, cache, jax.ShapeDtypeStruct(
                (B, T), jnp.int32)).compile().as_text()
            for fn, T in ((prefill, S), (decode, 1))}


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_program_is_named(compiled, program):
    assert compiled[program].startswith(f"HloModule jit_{program},")


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_layer_loop_compute_lies_in_layer_scopes(compiled, program):
    text = compiled[program]
    comps = _computations(text)
    body = re.search(r"\swhile\(.*?body=%([\w.\-]+)", text).group(1)
    compute = [line for line in comps[body] if _holds_compute(comps, line)]
    # q, k, v, o, the scores and the weighted values; the router and three
    # expert einsums and the combine
    assert len(compute) >= 11
    for line in compute:
        op_name = re.search(r'op_name="([^"]*)"', line)
        assert op_name, line
        path = op_name.group(1).split("/")
        assert path[0] == f"jit({program})"
        assert {"attention", "experts"} & set(path), line
    paths = " ".join(re.search(r'op_name="([^"]*)"', line).group(1)
                     for line in compute)
    assert "/experts/router/" in paths
