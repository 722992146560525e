"""The control at a small size: the plain reference put in the program's
place at fp8 (the precision below the configuration's bf16).

* ``test_control_fails_where_the_program_passes``: on the same seeded
  sample, the program stays within each of the cell's limits, and one of
  the numbers compared reads at least three times as high for the control;
* ``test_control_through_the_harness_is_incorrect``: the control, decoding
  greedily in the program's place, goes through a whole run of
  ``bench/run.py`` and comes out ``correct: false`` against the cell's own
  limits.

The limits themselves are set from readings on the chip at the cells' own
size (``bench/calibrate.py``; ``PERF.md`` gives them)."""
import types

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, check, run
from bench.tests.conftest import small_cell

WORKLOADS = ["granite-moe-3b-a800m.chat-prefill",
             "granite-moe-3b-a800m.decode-heavy"]
SEEDS = [3, 2**31 + 5, 2**33 + 7]


def control_generate(spec):
    """``generate`` of the control: greedy decoding with the reference in
    fp8.  The prompt and the tokens so far sit in one buffer of the final
    length; attention is causal, so its unfilled tail changes nothing."""
    fwd = jax.jit(check.reference(spec).logits, static_argnums=(1, 3, 4))

    def generate(params, cfg, prompts, *, gen):
        B, S = prompts.shape
        buf = jnp.concatenate([prompts, jnp.zeros((B, gen), jnp.int32)], 1)
        logits = []
        for i in range(gen + 1):
            at = fwd(params, spec, buf, gen + 1, True)[:, i]
            logits.append(at)
            if i < gen:
                buf = buf.at[:, S + i].set(jnp.argmax(at, -1).astype(
                    jnp.int32))
        return types.SimpleNamespace(tokens=buf[:, S:], logits=logits,
                                     compile_s=0.0, decode_s=0.0)
    return generate


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_where_the_program_passes(workload, seed):
    cell, program = small_cell(workload, check_requests=32)
    r = calibrate.readings(cell, program, seed, 3.0)
    prog, ctl = r["program"], r["control"]
    assert prog["positions"] >= 72, r
    assert all(prog[k] <= limit for k, limit in cell.limits.items()), r
    assert any(ctl[k] >= 3 * prog[k] and ctl[k] > 0
               for k in cell.limits), r


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_through_the_harness_is_incorrect(workload, seed):
    cell, program = small_cell(workload, check_requests=32)
    program.generate = control_generate(cell.config)
    result = run.run(cell, seed, 0.5, False, program=program,
                     require_chip=False)
    assert result["failed"] == 0
    assert result["checks"]["unservable"]["value"] == 0, result["checks"]
    assert result["correct"] is False, result["checks"]
