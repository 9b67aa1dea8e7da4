"""Runner `train`: one `paddle.jit.TrainStep` on one chip.

Traffic parameters: {"runner": "train", "batch": 2, "seq": 4096,
"reference_tokens": 512, "warmup_steps": 2, "traced_steps": 5}.
Configuration: the model's sizes at the top level and "trainer" (optimizer,
autocast, loss and attention settings).

Window: opens after the warm-up steps and closes at the end of the first
step that ends at or after `--seconds`; every step in it counts and the
rate is over that whole stretch, so no part of a step is ever dropped or
added. A traced run profiles `traced_steps` more steps after the window.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.harness.device import memory_peak_bytes
from benchmark.harness.job import Job, Measured, say, span, traced_window
from benchmark.harness.model import build_llama
from benchmark.reference import decoder as ref


def build_model(cell, seed):
    trainer = cell.config["trainer"]
    return build_llama(
        cell.config, seed,
        use_flash_attention=trainer["use_flash_attention"],
        recompute=trainer["recompute"],
        fused_linear_ce=trainer["fused_linear_ce"],
        fused_ce_chunks=trainer["fused_ce_chunks"])


def check_against_reference(net, model, rng, n_tokens):
    """The model's logits and fused-CE loss on one seeded sequence
    against the plain reference, before any training state exists."""
    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import unwrap

    ids = rng.integers(0, model["vocab_size"], (1, n_tokens)).astype(np.int64)
    labels = rng.integers(0, model["vocab_size"],
                          (1, n_tokens)).astype(np.int64)
    want = ref.logits(ref.model_weights(net), model, ids[0])
    want_loss = float(ref.mean_cross_entropy(want, labels[0]))
    with paddle.no_grad():
        got = unwrap(net(paddle.to_tensor(ids)))[0]
        got_loss = float(net(paddle.to_tensor(ids),
                             labels=paddle.to_tensor(labels)).numpy())
    err = ref.max_normalised_error(got, want)
    facts = dict(tokens=n_tokens, logits_error=err,
                 logits_tolerance=ref.LOGITS_TOL, loss=got_loss,
                 reference_loss=want_loss, loss_tolerance=ref.LOSS_TOL)
    say("reference", **facts)
    ok = (np.isfinite(err) and err <= ref.LOGITS_TOL
          and abs(got_loss - want_loss) <= ref.LOSS_TOL)
    return True if ok else f"model differs from the reference: {facts}"


def run(job: Job) -> Measured:
    import paddle_tpu as paddle
    from paddle_tpu.profiler.stats import CompileTracker

    cell, t = job.cell, job.cell.traffic
    model, trainer = cell.config, cell.config["trainer"]
    batch, seq = int(t["batch"]), int(t["seq"])
    rng = np.random.default_rng(job.seed)
    checks = {}

    cfg, net = build_model(cell, job.seed)
    say("model", params=net.num_params(),
        seconds=time.perf_counter() - job.process_start)
    checks["reference"] = check_against_reference(
        net, model, rng, int(t["reference_tokens"]))

    opt = paddle.optimizer.AdamW(
        trainer["learning_rate"], parameters=net.parameters(),
        moment_dtype=trainer["moment_dtype"])
    step = paddle.jit.TrainStep(net, lambda out, lab: out, opt,
                                amp_dtype=trainer["amp_dtype"])

    def next_batch():
        ids, labels = (paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
            for _ in range(2))
        return ids, labels

    ids, labels = next_batch()
    calls = step.lower((ids, labels), labels).as_text() \
        .count("tpu_custom_call")
    want_calls = int(trainer["pallas_calls_per_layer"]) \
        * model["num_hidden_layers"]
    on_tpu = job.device["platform"] == "tpu"
    checks["pallas_kernels_in_step"] = True if (
        calls >= want_calls or not on_tpu) else (
        f"the lowered step holds {calls} tpu_custom_call(s), "
        f"{want_calls} expected")

    warm_losses = []
    for _ in range(int(t["warmup_steps"])):
        t0 = time.perf_counter()
        warm_losses.append(float(step((ids, labels), labels).numpy()))
        say("warmup_step", seconds=time.perf_counter() - t0)
        ids, labels = next_batch()

    def one_step(batch_now):
        """Dispatch a step, build the next batch while the device works,
        then fetch the loss (which waits for the device)."""
        loss_t = step(batch_now, batch_now[1])
        with span("bench.next_batch"):
            nxt = next_batch()
        with span("bench.fetch_loss"):
            loss = float(loss_t.numpy())
        return loss, nxt

    tracker = CompileTracker().start()
    losses, ends = [], []
    start = time.perf_counter()
    setup_s = start - job.process_start
    now = start
    cur = (ids, labels)
    while now - start < job.seconds:
        loss, cur = one_step(cur)
        now = time.perf_counter()
        losses.append(loss)
        ends.append(now)
    compiles = tracker.compiles
    tracker.stop()
    window_s = ends[-1] - start
    hbm_peak = memory_peak_bytes(cell.chips)

    traced = {}
    if job.trace:
        with traced_window(job, traced):
            for _ in range(int(t["traced_steps"])):
                loss, cur = one_step(cur)
                losses.append(loss)

    say("losses", first=(warm_losses + losses)[:3], last=losses[-1],
        steps_in_window=len(ends), window_s=window_s)
    checks["losses_finite"] = True if all(
        np.isfinite(v) for v in warm_losses + losses) else \
        f"a loss is not finite: {warm_losses + losses}"
    checks["no_compile_in_window"] = True if compiles == 0 else \
        f"{compiles} compile(s) inside the measured window"

    tokens_per_step = batch * seq
    step_s = np.diff([start] + ends)
    return Measured(
        checks=checks, attempted=len(ends),
        failed=sum(1 for v in losses[:len(ends)] if not np.isfinite(v)),
        end_to_end={
            "train_tokens_per_s": len(ends) * tokens_per_step / window_s,
            "setup_s": setup_s},
        samples=dict(step_s=[float(s) for s in step_s],
                     tokens_per_step=tokens_per_step, batch=batch, seq=seq,
                     window_s=window_s, hbm_peak_bytes=hbm_peak,
                     traced_steps=int(t["traced_steps"])),
        trace=traced.get("trace"))
