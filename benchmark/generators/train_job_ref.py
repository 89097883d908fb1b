"""A training job for a configuration that names its own reference
(`"reference": "<module>"` in the configuration file, kind "train_job_ref" in
the traffic file).

The job is `train_job`'s to the letter: its `generate` (imported and used as
it is), one `fit()` epoch over the staged batches a round, rounds until the
window is spent, tokens / wall time of the rounds as the rate. `run` differs
from that file's in two things:

  * `correct` comes from `reference/train_check_ref.py`, which runs
    `train_check.py`'s four checks with the loss and gradient functions of
    the module the configuration names (benchmark/reference/<module>.py,
    `mean_loss_and_grads(params, x, y, wrt, sizes, trace)`), each group of
    checked weights against its own tolerance, plus two that an expert model
    brings (the selection bias is untouched; the router's choices against the
    reference's are counted);
  * `ctx` also carries the tokens of a step and the configuration's sizes,
    for the readers of the step's routing counts (`last_step_breakdown`),
    and in a traced run the graph op each instruction of the compiled step
    lies under (`step_scopes`, benchmark/train_trace.py).

The next architecture brings a reference file and no generator. The next
`benchmark` PR folds this file and `train_check_ref.py` into their twins
(PERF.md section 7).
"""

import math
import time

from benchmark.generators.train_job import generate  # noqa: F401


def run(h):
    import jax

    from benchmark.reference import train_check_ref
    from flexflow_tpu import SingleDataLoader

    traffic, seconds = h.traffic, h.seconds
    ff, tokens, _ = h.builder.build(h.config, h.cut, h.rehearsal)
    batch = ff.config.batch_size
    seq = tokens.dims[1]
    x, y = generate(traffic, h.args.seed, batch, seq, h.vocab)
    steps_per_round = x.shape[0] // batch
    tokens_per_round = x.shape[0] * seq
    SingleDataLoader(ff, tokens, x)
    SingleDataLoader(ff, ff.label_tensor, y)
    h.log(f"job: batch {batch} x seq {seq}, grad_accum "
          f"{ff.config.grad_accum_steps}, {steps_per_round} steps/round, "
          f"mesh {ff.config.mesh_shape}")

    # correctness: the reference's loss and gradient on batch 0 with the
    # initial weights (the step donates them, so before step 1), then step 1
    # through the program's own verbs and what it did to the weights
    ref = train_check_ref.reference(h, ff, x[:batch], y[:batch])
    t0 = time.perf_counter()
    ff.next_batch_all()
    ff.update()
    loss1 = float(ff._last_loss)
    update_errs = train_check_ref.update_errors(h, ff, ref)
    # one whole fit() round: every program of the window is compiled and the
    # data staged
    ff.fit(epochs=1, verbose=False)
    h.log(f"warm-up: step 1 + one fit() round in "
          f"{time.perf_counter() - t0:.1f} s; step-1 loss {loss1:.6f}, "
          f"reference {ref['loss']:.6f}")

    losses, round_s = [], []
    h.setup_done()
    t_win = time.perf_counter()
    while True:
        # a round cannot be interrupted: the slice starts with the first
        # round that will END inside it, however long a round is
        h.trace_poll(time.perf_counter() - t_win
                     + (round_s[-1] if round_s else 0.0))
        t_r = time.perf_counter()
        with h.annotate("bench.fit_round"):
            ff.fit(epochs=1, verbose=False)
            jax.block_until_ready(ff.params)
        round_s.append(time.perf_counter() - t_r)
        losses.append(float(ff._last_loss))
        if time.perf_counter() - t_win >= seconds:
            break
    h.window_done()
    wall = sum(round_s)
    steps = steps_per_round * len(round_s)
    rate = tokens_per_round * len(round_s) / wall
    h.log(f"window: {len(round_s)} rounds, {steps} steps, {wall:.3f} s in "
          f"fit(), {rate:.1f} tokens/s; round seconds min "
          f"{min(round_s):.4f} max {max(round_s):.4f}; last losses "
          f"{[round(v, 4) for v in losses[-3:]]}")

    check = train_check_ref.verdict(h, ff, loss1, ref, update_errs, losses)
    compiles = h.compiles_in_window()
    breakdown = getattr(ff, "last_step_breakdown", None) or {}
    h.log(f"last round's step breakdown: {breakdown}")
    sizes = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    scopes = None
    if h.args.trace and not h.rehearsal:
        # which graph op each instruction of the step lies under: the
        # device trace names instructions, the compiled text their scopes
        from benchmark import train_trace

        t0 = time.perf_counter()
        scopes = train_trace.scopes_of(
            train_trace.step_text(ff, {"input": x[:batch],
                                       "label": y[:batch]}),
            [op.name for op in ff.ops])
        h.log(f"the step's compiled text: {len(scopes)} instructions under "
              f"a graph op's name, in {time.perf_counter() - t0:.1f} s")
    return {
        "correct": bool(check and compiles == 0),
        "attempted": steps, "failed": sum(not math.isfinite(v)
                                          for v in losses),
        "end_to_end": {"train_tokens_per_s": rate},
        "ctx": {"mode": "train", "train_tokens_per_s": rate,
                "step_s": wall / steps, "steps": steps, "seq": seq,
                "tokens_per_step": batch * seq, "sizes": sizes,
                "layers": sizes["num_hidden_layers"],
                "chips": h.workload["chips"],
                "compiles_in_window": compiles,
                "last_step_breakdown": breakdown, "step_scopes": scopes},
    }
