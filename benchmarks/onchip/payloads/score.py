"""Scoring: one forward of the model over a batch of token ids, reduced on
the device to the mean log-probability of each row's tokens given their
prefixes, handed to the host as floats (an ensemble member scoring a
candidate). The forward is the system's ``models.model.forward``."""
from __future__ import annotations

import numpy as np

from harness import flops
from harness.seeds import rng


class Payload:
    name = "score"

    def __init__(self, run, spec):
        self.run = run
        self.model = run.model(spec["model"])
        self.batch = int(spec["batch"])
        self.seq = int(spec["seq_len"])
        self.flops = flops.mamba2_forward(self.model.m, self.batch, self.seq) \
            if self.model.family == "ssm" else flops.dense_forward(
                self.model.m, self.batch, self.seq)

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.models import model as M
        cfg = self.model.cfg
        self.weight_seed = self.run.derive(1)
        self.params = jax.jit(lambda k: M.init_params(k, cfg))(
            jax.random.PRNGKey(self.weight_seed))
        pos = jnp.broadcast_to(jnp.arange(self.seq, dtype=jnp.int32)[None],
                               (self.batch, self.seq))

        def score(params, tokens):
            lg = M.forward(params, cfg, {"tokens": tokens, "positions": pos},
                           mode="train")[0].astype(jnp.float32)
            lp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
            got = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)
            return jnp.mean(got[..., 0], axis=-1)

        self._score = jax.jit(score)
        self.fn(-1, self.tokens(0, 0))            # the one shape, warmed

    def tokens(self, client: int, k: int) -> np.ndarray:
        return rng(self.run.seed, 2, client, k).integers(
            0, self.model.m["vocab_size"], (self.batch, self.seq),
            dtype=np.int32)

    def fn(self, rid: int, tokens: np.ndarray):
        """The task: (request id, host scores)."""
        import jax
        with jax.profiler.TraceAnnotation("bench:exec:score"):
            return rid, np.asarray(self._score(self.params, tokens))

    def free(self):
        self.params = None
        self._score = None
