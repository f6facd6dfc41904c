#!/usr/bin/env python3
"""Chip smoke test: the hybrid campaign's real engine on one TPU chip.

    python3 chip_smoke.py              # one chip: kernels, then the campaign
    python3 chip_smoke.py --chips 4    # four chips: flux partitions only

One process holds the chip and runs everything; no phase falls back to the
CPU. Phases, each checked against a reference:

* kernels   — every Pallas kernel once at real widths (flash and decode
              attention at stablelm-3b's, SSD at mamba2-130m's, rmsnorm at
              d=2560) against its ``ref.py``.
* campaign  — one ``Session(mode="real")`` pilot with dragon threads and one
              flux partition over the host mesh, as
              ``examples/hybrid_campaign.py`` builds it: 64 docking
              functions, a 3-step full-size mamba2-130m ``train`` on the
              partition, and a full-size stablelm-3b ``generate`` with the
              Pallas kernels, its prefill logits compared with the XLA path.
* partitions (``--chips 4`` only) — two concurrent mamba2-130m ``train``
              tasks, each on its own 2-chip flux partition, against the same
              training on one chip alone.

Weights and data are random, made from ``--seed``. Any failure exits
nonzero. The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs import get_config                          # noqa: E402
from repro.core import (PilotDescription, PilotManager,       # noqa: E402
                        Session, TaskDescription, TaskManager)
from repro.launch import serve                                # noqa: E402
from repro.launch.compile_cache import enable_compile_cache   # noqa: E402
from repro.launch.mesh import make_host_mesh, submesh         # noqa: E402
from repro.launch.train import train                          # noqa: E402
from repro.models import model as M                           # noqa: E402

# kernel widths: stablelm-3b attention (32 heads x 80, S=2048), mamba2-130m
# SSD (24 heads, P=64, N=128, chunk 256), stablelm-3b rmsnorm (d=2560)
KERNEL_WIDTHS = {"batch": 2, "seq": 2048, "heads": 32, "head_dim": 80,
                 "ssd_heads": 24, "ssd_p": 64, "ssd_n": 128, "chunk": 256,
                 "d_model": 2560}
# max |kernel - ref| / max |ref|, inputs in bf16 as the models use them
KERNEL_TOL = 2e-2
# ||logits_pallas - logits_xla|| / ||logits_xla||: bf16 rounds each of the 32
# layers' outputs at 2^-8, so independent roundings drift ~sqrt(32) * 2^-8
LOGITS_TOL = 5e-2
# one-chip vs two-chip data-parallel losses: same batches and seed, only the
# order of the gradient reduction differs
LOSS_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Seconds JAX spends compiling (or fetching from the persistent cache),
    read per phase from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str, out: dict):
        s0, c0, h0 = self.seconds, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        try:
            yield
        finally:
            out[name] = {"wall_s": time.perf_counter() - t0,
                         "compile_s": self.seconds - s0,
                         "compiles": self.compiles - c0,
                         "cache_hits": self.cache_hits - h0}
            log(f"phase {name}: wall {out[name]['wall_s']:.3f}s, compile "
                f"{out[name]['compile_s']:.3f}s over "
                f"{out[name]['compiles']} compiles "
                f"({out[name]['cache_hits']} persistent-cache hits)")


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def run_kernels(widths=KERNEL_WIDTHS, *, interpret: bool = False) -> dict:
    """Each Pallas kernel once against its reference; returns the errors."""
    from repro.kernels.decode_attention import ref as da_ref
    from repro.kernels.decode_attention.decode_attention import \
        decode_attention_bhd
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_bhsd
    from repro.kernels.fused_rmsnorm import ref as rn_ref
    from repro.kernels.fused_rmsnorm.fused_rmsnorm import fused_rmsnorm
    from repro.kernels.ssd import ref as ssd_ref
    from repro.kernels.ssd.ssd import ssd_pallas

    w = widths
    B, S, H, hd = w["batch"], w["seq"], w["heads"], w["head_dim"]
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    scale = 1.0 / float(np.sqrt(hd))
    errs = {}
    # references at full f32 matmul precision, so the error is the kernel's
    exact = jax.default_matmul_precision("highest")

    q = jax.random.normal(ks[0], (B, H, S, hd), bf)
    k = jax.random.normal(ks[1], (B, H, S, hd), bf)
    v = jax.random.normal(ks[2], (B, H, S, hd), bf)
    got = flash_attention_bhsd(q, k, v, scale=scale, interpret=interpret)
    with exact:
        want = fa_ref.attention_ref(q, k, v, scale=scale, causal=True)
    errs["flash_attention"] = _rel_err(got, want)

    q1 = q[:, :, :1]
    valid = S - S // 4
    kc, vc = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)   # decode's cache
    got = decode_attention_bhd(q1, kc, vc, valid, scale=scale,
                               interpret=interpret)
    with exact:
        want = da_ref.decode_attention_ref(q1, kc, vc, valid, scale=scale)
    errs["decode_attention"] = _rel_err(got, want)

    Hs, P, N = w["ssd_heads"], w["ssd_p"], w["ssd_n"]
    x = jax.random.normal(ks[3], (B, S, Hs, P), bf)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, S, Hs)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[5], (Hs,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[6], (B, S, 1, N), bf)
    Cm = jax.random.normal(ks[7], (B, S, 1, N), bf)
    y, h = ssd_pallas(x, dt, A, Bm, Cm, chunk=w["chunk"], interpret=interpret)
    with exact:
        y0, h0 = ssd_ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=w["chunk"])
    errs["ssd"] = max(_rel_err(y, y0), _rel_err(h, h0))

    d = w["d_model"]
    xn = jax.random.normal(ks[8], (B * S, d), bf)
    wn = (jax.random.normal(ks[9], (d,)) * 0.1).astype(bf)
    got = fused_rmsnorm(xn, wn, interpret=interpret)
    errs["fused_rmsnorm"] = _rel_err(got, rn_ref.rmsnorm_ref(xn, wn))

    for name, err in errs.items():
        log(f"kernel {name}: max err {err:.3e} (tolerance {KERNEL_TOL:.0e})")
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
    check(not bad, f"kernels outside tolerance: {bad}")
    return errs


# ----------------------------------------------------------------- payloads
def docking(mol) -> float:
    """CPU-bound scoring stand-in (the AutoDock analogue)."""
    return float(np.sum(np.sin(mol) ** 2))


def train_payload(cfg, steps, global_batch, seq_len, seed, mesh=None):
    """Flux payload: ``train`` on the partition's mesh (flux passes it as
    ``mesh``); host values only."""
    out = train(cfg, steps=steps, global_batch=global_batch,
                seq_len=seq_len, mesh=mesh, seed=seed, quiet=True)
    devices = sorted({d.id for leaf in jax.tree.leaves(out["params"])
                      for d in leaf.devices()})
    return {"losses": [float(x) for x in out["losses"]],
            "param_devices": devices,
            "mesh_devices": sorted(int(d.id) for d in np.ravel(mesh.devices))}


def inference_payload(cfg, n_prompts, prompt_len, new_tokens, seed,
                      interpret=False):
    """Dragon payload: ``generate`` with the Pallas kernels, and the prefill
    logits of the kernels against the XLA path on the same params."""
    ctx = contextlib.nullcontext()
    if interpret:
        from jax.experimental.pallas import tpu as pltpu
        ctx = pltpu.force_tpu_interpret_mode()
    with ctx:
        kp, kt = jax.random.split(jax.random.PRNGKey(seed))
        params = jax.jit(lambda key: M.init_params(key, cfg))(kp)
        prompts = jax.random.randint(kt, (n_prompts, prompt_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
        tokens = np.asarray(serve.generate(params, cfg, prompts,
                                           max_new_tokens=new_tokens))
        batch = {"tokens": prompts,
                 "positions": serve.positions(cfg, n_prompts, prompt_len)}
        xla = dataclasses.replace(cfg, use_pallas=False)
        lp = np.asarray(serve.serve_steps(cfg)[0](params, batch)[0],
                        np.float32)
        lx = np.asarray(serve.serve_steps(xla)[0](params, batch)[0],
                        np.float32)
    return {"tokens": tokens,
            "logits_rel_l2": float(np.linalg.norm(lp - lx)
                                   / (np.linalg.norm(lx) + 1e-30)),
            "logits_max_abs": float(np.max(np.abs(lp - lx))),
            "argmax_agree": float(np.mean(lp.argmax(-1) == lx.argmax(-1))),
            "finite": bool(np.isfinite(lp).all() and np.isfinite(lx).all())}


# ----------------------------------------------------------------- campaign
def _run_stage(tmgr, name, descs, timeout, meter, times) -> list:
    with meter.phase(name, times):
        tasks = tmgr.submit_tasks(descs)
        finished = tmgr.wait_tasks(tasks, timeout=timeout)
    states = collections.Counter(t.state.value for t in tasks)
    log(f"stage {name}: {dict(states)} on backends "
        f"{sorted({str(t.backend) for t in tasks})}")
    for t in tasks:
        if t.state.value != "DONE":
            log(f"stage {name}: task {t.uid} {t.state.value}: {t.error}")
    check(finished, f"stage {name} did not finish within {timeout}s")
    check(all(t.state.value == "DONE" and t.result is not None
              for t in tasks), f"stage {name}: not every task DONE")
    return tasks


def run_campaign(train_cfg, serve_cfg, *, n_docking=64, train_steps=3,
                 train_batch=4, train_seq=2048, n_prompts=8, prompt_len=512,
                 new_tokens=16, seed=0, interpret=False, meter=None,
                 times=None) -> dict:
    """The hybrid campaign through ``Session(mode="real")``: docking on
    dragon threads, training on a flux partition, inference on dragon."""
    meter = meter or CompileMeter()
    times = times if times is not None else {}
    log(f"sst_train runs {train_cfg.name} with use_pallas="
        f"{train_cfg.use_pallas}: the XLA attention/SSD path, because no "
        "Pallas kernel has a backward pass")
    rng = np.random.default_rng(seed)
    with Session(mode="real") as session:
        pilot = PilotManager(session).submit_pilots(PilotDescription(
            nodes=1, backends={"dragon": {"workers": 4},
                               "flux": {"partitions": 1,
                                        "mesh": make_host_mesh()}}))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)

        dock = _run_stage(tmgr, "docking", [
            TaskDescription(kind="function", fn=docking, args=(m,),
                            stage="docking")
            for m in rng.standard_normal((n_docking, 8))], 300, meter, times)

        (tr,) = _run_stage(tmgr, "sst_train", [TaskDescription(
            kind="executable", coupling="tight", fn=train_payload,
            args=(train_cfg, train_steps, train_batch, train_seq, seed),
            stage="sst_train")], 1200, meter, times)
        losses = tr.result["losses"]
        log(f"sst_train losses {losses}")
        check(len(losses) == train_steps and np.isfinite(losses).all(),
              f"sst_train losses not finite: {losses}")

        (inf,) = _run_stage(tmgr, "inference", [TaskDescription(
            kind="function", fn=inference_payload,
            args=(serve_cfg, n_prompts, prompt_len, new_tokens, seed),
            kwargs={"interpret": interpret}, stage="inference")],
            1200, meter, times)
        r = inf.result
        toks = r["tokens"]
        log(f"inference tokens {toks.shape}, range [{toks.min()}, "
            f"{toks.max()}], prefill logits pallas vs xla: rel l2 "
            f"{r['logits_rel_l2']:.3e} (tolerance {LOGITS_TOL:.0e}), max abs "
            f"{r['logits_max_abs']:.3e}, argmax agreement "
            f"{r['argmax_agree']:.3f}")
        check(toks.shape == (n_prompts, prompt_len + new_tokens),
              f"inference output shape {toks.shape}")
        check(0 <= toks.min() and toks.max() < serve_cfg.vocab_size,
              "inference tokens out of vocabulary range")
        check(r["finite"] and r["logits_rel_l2"] <= LOGITS_TOL,
              "prefill logits: pallas and xla paths disagree")
    return {"docking": [t.result for t in dock], "losses": losses,
            "inference": r, "times": times}


def run_partitions(cfg, *, steps=3, global_batch=8, seq_len=2048, seed=0,
                   meter=None, times=None) -> dict:
    """Two concurrent ``train`` tasks on two flux partitions (submeshes of
    the host mesh), against the same training on one chip alone."""
    meter = meter or CompileMeter()
    times = times if times is not None else {}
    mesh = make_host_mesh()
    with Session(mode="real") as session:
        pilot = PilotManager(session).submit_pilots(PilotDescription(
            nodes=1, backends={"flux": {"partitions": 2, "mesh": mesh}}))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)
        tasks = _run_stage(tmgr, "partitions", [TaskDescription(
            kind="executable", coupling="tight", fn=train_payload,
            args=(cfg, steps, global_batch, seq_len, seed),
            stage="partitions") for _ in range(2)], 1200, meter, times)
    with meter.phase("one_chip", times):
        alone = train_payload(cfg, steps, global_batch, seq_len, seed,
                              mesh=submesh(mesh, "data", 0, 1))
    parts = []
    # report in partition order: which task a free partition picks up
    # first is a race between the two workers
    for t in sorted(tasks, key=lambda t: t.partition):
        r = t.result
        log(f"partition {t.partition}: devices {r['mesh_devices']}, params "
            f"on {r['param_devices']}, losses {r['losses']}")
        check(set(r["param_devices"]) <= set(r["mesh_devices"]),
              f"partition {t.partition}: params left its devices")
        parts.append(r)
    check(not set(parts[0]["mesh_devices"]) & set(parts[1]["mesh_devices"]),
          "the two partitions share devices")
    diff = max(abs(a - b) for r in parts
               for a, b in zip(r["losses"], alone["losses"]))
    log(f"one chip {alone['mesh_devices']}: losses {alone['losses']}; max "
        f"|partition - one chip| {diff:.3e} (tolerance {LOSS_TOL:.0e})")
    check(all(np.isfinite(r["losses"]).all() for r in parts + [alone]),
          "partition losses not finite")
    check(diff <= LOSS_TOL, "partition losses disagree with one chip")
    return {"partitions": parts, "one_chip": alone, "max_diff": diff,
            "times": times}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the two-partition phase and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    log(f"devices: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev['platform']!r}", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {dev['count']}", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    times = {}
    try:
        if args.chips == 4:
            run_partitions(get_config("mamba2-130m"), seed=args.seed,
                           meter=meter, times=times)
        else:
            with meter.phase("kernels", times):
                run_kernels()
            run_campaign(get_config("mamba2-130m"),
                         get_config("stablelm-3b", use_pallas=True),
                         seed=args.seed, meter=meter, times=times)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log("phase times: " + json.dumps(times))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
