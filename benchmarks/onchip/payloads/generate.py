"""Surrogate inference: the system's ``launch.serve.generate`` (prefill and
greedy decode through the cache, with the Pallas kernels where the model
asks for them) over a batch of prompts, with parameters made once in set-up
and held resident on the chip, as a deployed surrogate holds them."""
from __future__ import annotations

import numpy as np

from harness import flops
from harness.seeds import rng


class Payload:
    name = "generate"

    def __init__(self, run, spec):
        self.run = run
        self.model = run.model(spec["model"])
        self.prompts = int(spec["prompts"])
        self.prompt_len = int(spec["prompt_len"])
        self.new = int(spec["new_tokens"])
        self.flops = flops.generate(self.model.m, self.prompts,
                                    self.prompt_len, self.new)

    def setup(self):
        import jax
        from repro.models import model as M
        cfg = self.model.cfg
        self.weight_seed = self.run.derive(4)
        self.params = jax.jit(lambda k: M.init_params(k, cfg))(
            jax.random.PRNGKey(self.weight_seed))
        self.fn(self.prompt_tokens(-1))           # the shapes, warmed

    def prompt_tokens(self, it: int) -> np.ndarray:
        return rng(self.run.seed, 5, it + 1).integers(
            0, self.model.m["vocab_size"], (self.prompts, self.prompt_len),
            dtype=np.int32)

    def fn(self, prompts: np.ndarray) -> np.ndarray:
        """The task: the prompts and their greedy continuations, on the host."""
        import jax
        from repro.launch import serve
        with jax.profiler.TraceAnnotation("bench:exec:generate"):
            return np.asarray(serve.generate(self.params, self.model.cfg,
                                             prompts,
                                             max_new_tokens=self.new))

    def free(self):
        self.params = None
