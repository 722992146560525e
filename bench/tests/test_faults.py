"""A whole run of ``bench/run.py`` on the CPU at a small size, with the
timed path broken underneath: ``correct`` must come out false for each
fault a serving cell can have, and true with none.

* ``stale_state``: the model step returns the cache it was given (prefill
  and decode leave the state unchanged);
* ``half_batch``: ``generate`` serves the first half of the batch and
  hands its outputs to the second half as well;
* ``altered_token``: one served token of every request is changed where
  ``generate`` produces it;
* ``pad_token``: one served token of every request is a padded row of the
  vocabulary table (an id at or above ``vocab_size``), as a head that lost
  its pad mask would serve.

The cells here run on one chip, so there is no exchange between chips to
leave out.
"""
import dataclasses
from unittest import mock

import pytest

from bench import run
from bench.tests.conftest import small_cell


def _sound(generate):
    return generate


def _stale_state(generate):
    from repro.model import lm
    real = lm.step

    def step(params, cfg, cache, tokens, **kw):
        logits, _ = real(params, cfg, cache, tokens, **kw)
        return logits, cache

    def broken(params, cfg, prompts, *, gen, **kw):
        with mock.patch.object(lm, "step", step):
            return generate(params, cfg, prompts, gen=gen, **kw)
    return broken


def _half_batch(generate):
    import jax.numpy as jnp

    def broken(params, cfg, prompts, *, gen, **kw):
        B = prompts.shape[0]
        out = generate(params, cfg, prompts[:B // 2], gen=gen, **kw)

        def both(a):
            return jnp.concatenate([a, a], 0)[:B]
        return dataclasses.replace(out, tokens=both(out.tokens),
                                   logits=[both(x) for x in out.logits])
    return broken


def _altered_token(generate):
    def broken(params, cfg, prompts, *, gen, **kw):
        out = generate(params, cfg, prompts, gen=gen, **kw)
        i = gen // 2
        tokens = out.tokens.at[:, i].set((out.tokens[:, i] + 1) % cfg.vocab)
        return dataclasses.replace(out, tokens=tokens)
    return broken


def _pad_token(generate):
    def broken(params, cfg, prompts, *, gen, **kw):
        out = generate(params, cfg, prompts, gen=gen, **kw)
        tokens = out.tokens.at[:, gen // 2].set(cfg.vocab + 3)
        return dataclasses.replace(out, tokens=tokens)
    return broken


FAULTS = {"sound": (_sound, True), "stale_state": (_stale_state, False),
          "half_batch": (_half_batch, False),
          "altered_token": (_altered_token, False),
          "pad_token": (_pad_token, False)}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ["granite-moe-3b-a800m.chat-prefill",
                                      "granite-moe-3b-a800m.decode-heavy"])
def test_fault_is_caught(workload, fault):
    cell, program = small_cell(workload, check_requests=64)
    wrap, want = FAULTS[fault]
    program.generate = wrap(program.generate)
    result = run.run(cell, 2**33 + 17, 0.5, False, program=program,
                     require_chip=False)
    assert result["correct"] is want, result["checks"]
    assert result["failed"] == 0
    if fault == "pad_token":
        assert result["checks"]["unservable"]["value"] > 0, result["checks"]
