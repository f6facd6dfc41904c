"""Generation in a closed loop with a zamba2 hybrid: each task is one call
of the system's ``launch.serve.generate`` (prefill, then greedy decode through the cache,
with the Pallas kernels where the model asks for them) over a batch of
prompts drawn from the seed for its client and request. Parameters are made
once in set-up and held resident on the chip, as a deployed model holds
them. The chip serves one task's ``generate`` at a time, in the order the
tasks reached it, as one model server serves its batches: the runtime's
four threads interleaving prefills and decode steps on one chip add no
work done and leave each task's wait to the order its steps happened to
reach the device in. Each task hands back its request id, the prompts with
their continuations, and the stamps and counters ``generate`` recorded."""
from __future__ import annotations

import gc
import threading

import numpy as np

from harness import flops_zamba2
from harness.seeds import rng


class Turns:
    """A lock granted in the order it was asked for."""

    def __init__(self):
        self._cv = threading.Condition()
        self._asked = 0
        self._serving = 0

    def __enter__(self):
        with self._cv:
            mine = self._asked
            self._asked += 1
            self._cv.wait_for(lambda: self._serving == mine)

    def __exit__(self, *exc):
        with self._cv:
            self._serving += 1
            self._cv.notify_all()


class Payload:
    name = "generate_loop"

    def __init__(self, run, spec):
        self.run = run
        self.model = run.model(spec["model"])
        self.prompts = int(spec["prompts"])
        self.prompt_len = int(spec["prompt_len"])
        self.new = int(spec["new_tokens"])
        self.flops = flops_zamba2.generate(self.model.m, self.prompts,
                                           self.prompt_len, self.new)
        self.chip = Turns()

    def setup(self):
        import jax
        from repro.models import model as M
        cfg = self.model.cfg
        self.weight_seed = self.run.derive(6)
        self.params = jax.jit(lambda k: M.init_params(k, cfg))(
            jax.random.PRNGKey(self.weight_seed))
        self.fn(-1, self.tokens(1 << 20, 0))     # the shapes, warmed
        # what start-up left alive (JAX, the compiled programs, the model)
        # stays out of the collector's full passes, as a long-running
        # server's start-up heap does: a pass over it stalls the host for
        # 50-130 ms, and the stall lands on every task in flight
        gc.collect()
        gc.freeze()

    def tokens(self, client: int, k: int) -> np.ndarray:
        return rng(self.run.seed, 7, client, k).integers(
            0, self.model.m["vocab_size"], (self.prompts, self.prompt_len),
            dtype=np.int32)

    def fn(self, rid: int, prompts: np.ndarray):
        """The task: (request id, prompts and continuations on the host,
        ``generate``'s stamps)."""
        import jax
        from repro.launch import serve
        stamps = {}
        with self.chip, jax.profiler.TraceAnnotation(
                "bench:exec:generate_loop"):
            out = np.asarray(serve.generate(
                self.params, self.model.cfg, prompts,
                max_new_tokens=self.new, stamps=stamps))
        return rid, out, stamps

    def free(self):
        self.params = None
