"""Whole runs on the CPU at small sizes, the chip's look skipped: a sound
run comes out ``correct``, and a run whose timed path is broken underneath
(in the system's own code, where the fault would be made) comes out not
correct: an answer altered (stream), a training step that returns its state
unchanged, a training step that leaves half of each batch out and a served
token altered (campaign)."""
import jax.numpy as jnp

from tests.small import run_cell


def test_sound_stream_is_correct():
    res, _ = run_cell("stream.short", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for m in ("dispatch_ms.stream", "queue_ms.stream", "exec_ms.stream",
              "mfu.stream"):
        assert res["metrics"][m]["value"] > 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]


def test_stream_answer_altered(monkeypatch):
    import repro.models.model as M
    forward = M.forward

    def altered(*a, **k):            # every token's logit moved to the next
        logits, aux, cache = forward(*a, **k)
        return jnp.roll(logits, 1, axis=-1), aux, cache

    monkeypatch.setattr(M, "forward", altered)
    res, err = run_cell("stream.short")
    assert not res["correct"]
    assert "score_gap" in err.strip().splitlines()[-1]


def test_sound_campaign_is_correct():
    res, _ = run_cell("campaign.impeccable", trace=True)
    assert res["correct"], res["checks"]
    for m in ("overhead_s.campaign", "compile_s.campaign", "mfu.campaign"):
        assert m in res["metrics"]


def test_campaign_step_returns_state_unchanged(monkeypatch):
    import repro.optim.adamw as A
    update = A.update

    def unchanged(cfg, state, grads, params):
        _, new_state, metrics = update(cfg, state, grads, params)
        return params, new_state, metrics

    monkeypatch.setattr(A, "update", unchanged)
    res, _ = run_cell("campaign.impeccable")
    assert not res["correct"]
    c = res["checks"]["change_gap"]
    assert c["value"] > 0.9 and c["value"] > c["limit"]


def test_campaign_step_leaves_half_the_batch_out(monkeypatch):
    import repro.distributed.train_step as TS
    make_loss_fn = TS.make_loss_fn

    def half(cfg):
        loss_fn = make_loss_fn(cfg)

        def first_half(params, batch):   # the mean over the rest
            return loss_fn(params, {k: v[:v.shape[0] // 2]
                                    for k, v in batch.items()})
        return first_half

    monkeypatch.setattr(TS, "make_loss_fn", half)
    res, _ = run_cell("campaign.impeccable")
    assert not res["correct"]
    c = res["checks"]
    assert any(c[k]["value"] > c[k]["limit"] for k in
               ("loss_gap", "change_gap")), c


def test_campaign_token_altered(monkeypatch):
    import repro.launch.serve as SV
    sample = SV.sample
    calls = []

    def altered(logits, key, temperature=0.0, vocab_size=0):
        tok = sample(logits, key, temperature, vocab_size)
        calls.append(1)
        if len(calls) % 3 == 2:         # one served token in three
            tok = (tok + 1) % vocab_size
        return tok

    monkeypatch.setattr(SV, "sample", altered)
    res, _ = run_cell("campaign.impeccable")
    assert not res["correct"]
    assert res["checks"]["served_gap"]["value"] > res["checks"][
        "served_gap"]["limit"]
