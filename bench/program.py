"""The system under test, as the benchmark sees it: the program's config
for a model, its parameter layout, and ``repro.launch.serve.generate``.
This is the only module of the benchmark that imports the program."""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax


@dataclasses.dataclass
class Program:
    cfg: object
    generate: Callable
    #: the shapes and dtypes of ``lm.init_params(cfg)``
    layout: object

    def check_layout(self, params) -> None:
        """The benchmark's weights must have the program's layout."""
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), self.layout)
        if got != want:
            raise SystemExit("the program's parameter layout differs from "
                             "the benchmark's weights: a benchmark PR must "
                             "follow it")


def load(spec: dict, cfg=None, generate=None) -> Program:
    """The program for the configuration file ``spec``: its ``program``
    block names the program's config, whose pinned fields must agree (a
    later edit of the program's config cannot move the yardstick).
    ``cfg``/``generate`` replace the program's own (the tests' small
    sizes and planted faults)."""
    from repro import compile_cache, configs
    from repro.launch import serve
    from repro.model import lm

    compile_cache.configure()
    if cfg is None:
        cfg = configs.get(spec["program"]["arch"])
        wrong = {k: (getattr(cfg, k), v)
                 for k, v in spec["program"]["pins"].items()
                 if getattr(cfg, k) != v}
        if wrong:
            raise SystemExit(f"program config {cfg.name} disagrees with "
                             f"the pinned sizes (got, pinned): {wrong}")
    layout = jax.eval_shape(functools.partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    return Program(cfg=cfg, generate=generate or serve.generate,
                   layout=layout)
