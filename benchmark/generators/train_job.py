"""A training job: `fit()` rounds over device-resident seeded token data
until the window is spent (`--seconds`, or the traffic file's `max_seconds`
where that is less: a cell that is steady sooner ends sooner).

The traffic file (kind "train_job") gives the number of distinct batches;
the configuration's cut gives the sequence length and the batch. Tokens are
uniform over the vocabulary, drawn from `--seed`; labels are the next token.
One round is one `fit()` epoch over the staged batches and ends in
`block_until_ready` (fit() itself blocks on the parameters before it
returns), so tokens / wall time of the rounds is a device rate.
"""

import math
import time

import numpy as np


def generate(traffic, seed, batch, seq, vocab):
    """(x, y): `distinct_batches` x `batch` sequences of `seq` tokens and
    their next-token labels, the same for the same seed."""
    n = int(traffic["distinct_batches"]) * batch
    rng = np.random.default_rng([int(seed), 0x7EA1])
    toks = rng.integers(0, vocab, size=(n, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:, None]


def run(h):
    import jax

    from benchmark.reference import train_check
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
    summary = getattr(ff, "_search_summary", None) or {}
    h.log(f"job: batch {batch} x seq {seq}, grad_accum "
          f"{ff.config.grad_accum_steps}, {steps_per_round} steps/round, "
          f"mesh {ff.config.mesh_shape}, search "
          f"{ {k: summary.get(k) for k in ('simulator', 'predicted_step_s', 'peak_hbm_bytes')} }")

    # correctness: the reference's loss and gradient on batch 0 with the
    # initial weights (the step donates them, so before step 1), then step 1
    # through the program's own verbs and what it did to the weights
    ref = train_check.reference(h, ff, x[:batch], y[:batch])
    t0 = time.perf_counter()
    ff.next_batch_all()
    ff.update()
    loss1 = float(ff._last_loss)
    update_err = train_check.update_error(h, ff, ref)
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

    check = train_check.verdict(h, ff, loss1, ref, update_err,
                                losses)
    compiles = h.compiles_in_window()
    breakdown = getattr(ff, "last_step_breakdown", None) or {}
    return {
        "correct": bool(check and compiles == 0),
        "attempted": steps, "failed": sum(not math.isfinite(v)
                                          for v in losses),
        "end_to_end": {"train_tokens_per_s": rate},
        "ctx": {"mode": "train", "train_tokens_per_s": rate,
                "step_s": wall / steps, "steps": steps, "seq": seq,
                "layers": h.builder.sizes_of(h.config, h.cut,
                                             h.rehearsal)["num_hidden_layers"],
                "chips": h.workload["chips"],
                "compiles_in_window": compiles,
                "last_step_breakdown": breakdown,
                "search_summary": summary},
    }
