"""Controls for the limits of `moe-mla-train-4k`'s `correct`, and the two
lowerings this cell's step is recorded beside: each variant builds the cell's
model through its own builder, plants one fault (or none) in the program,
and runs the cell's own checks (`reference/train_check_ref.py`: the named
reference's loss and gradient on batch 0, step 1 through `next_batch_all()` /
`update()`, the per-weight Adam errors), then a few `fit()` rounds for the
step's time. A limit of the configuration file lies between the largest
reading the sound program gives and the smallest a control gives; this script
is where the second kind of reading comes from.

What is planted (each in a model of its own, in this order):

  sound          nothing; also counts the router's flipped choices per expert
                 layer (`train_check_ref.router_flip_shares`, a forward
                 program of its own that the cell does not compile)
  f32_compute    `compute_dtype` float32: the same program with no bf16 in it
                 (what of the sound program's readings is rounding and
                 flipped router choices, and what is not)
  master_bf16    `master_dtype` bfloat16: weights and moments in 16 bits, the
                 nearest precision below what the configuration states; must
                 fail (b)
  zero_grads     the gradients of `moe_1.router` and of the held
                 `moe_1.w_down` zeroed before the optimizer sees them; each
                 must fail (b) alone
  biased_gates   `ops/moe.py` `_route` takes the gates from s' = s + b
                 instead of s; must fail (a) or (b)
  all_rows       `ops/moe.py` `held_rows_cap` = N*k: the held share in the
                 N*k-row form the parent commit ran (timing and memory; what
                 its gradient holds on the chip is read, not assumed)
  wide_pass      `ops/moe.py` `HELD_ROWS_SLACK` 7.9: one pass of nearly all
                 N*k sorted rows through the sound, masked passes: what
                 working on N*k rows costs beside working on a quarter of
                 them, with the routing and the numbers equal
  xla_attention  `use_flash_attention` false: the latent attention's blocked
                 XLA form under the same step (timing and memory, if the
                 compiler places it at all)

Everything is written to chiprun_out/kanana_controls.json as it is read.

    python3 benchmark/kanana_controls.py --seed 3000003301 [--rounds 2]
        [--only sound,master_bf16] [--rehearsal]
"""

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run, spec  # noqa: E402

CELL = "moe-mla-train-4k"
CONTROLS = ("sound", "f32_compute", "master_bf16", "zero_grads",
            "biased_gates", "all_rows", "wide_pass", "xla_attention")
ZEROED = (("moe_1", "router"), ("moe_1", "w_down"))
OUT = os.path.join(ROOT, "chiprun_out", "kanana_controls.json")


def biased_route():
    """`MoE._route` with the gates taken from the biased scores."""
    import jax
    import jax.numpy as jnp

    def route(self, params, t):
        scores = jax.nn.sigmoid(jnp.dot(
            t.astype(jnp.float32), params["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        sel = scores + params["score_bias"].astype(jnp.float32)
        top_g, top_e = jax.lax.top_k(sel, self.k)       # gates from s + b
        top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
        return scores, top_g * self.routed_scaling, top_e

    return route


def run_one(name, h, x, y, rounds):
    """Build, plant, check, time. Returns the readings."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import train_check_ref as check
    from flexflow_tpu import SingleDataLoader
    from flexflow_tpu.ops import moe

    undo = []
    cut = dict(h.cut)
    if name == "master_bf16":
        cut["ffconfig"] = dict(cut["ffconfig"], master_dtype="bfloat16")
    if name == "f32_compute":
        cut["ffconfig"] = dict(cut["ffconfig"], compute_dtype="float32")
    if name == "xla_attention":
        cut["ffconfig"] = dict(cut["ffconfig"], use_flash_attention=False)
    if name == "biased_gates":
        real = moe.MoE._route
        moe.MoE._route = biased_route()
        undo.append(lambda: setattr(moe.MoE, "_route", real))
    if name == "all_rows":
        real_cap = moe.held_rows_cap
        moe.held_rows_cap = lambda n, k, held, e: n * k
        undo.append(lambda: setattr(moe, "held_rows_cap", real_cap))
    if name == "wide_pass":
        slack = moe.HELD_ROWS_SLACK
        moe.HELD_ROWS_SLACK = 7.9
        undo.append(lambda: setattr(moe, "HELD_ROWS_SLACK", slack))
    out = {"control": name}
    ff = None
    try:
        t0 = time.perf_counter()
        ff, tokens, _ = h.builder.build(h.config, cut, h.rehearsal)
        if name == "zero_grads":
            opt, real_update = ff.optimizer, ff.optimizer.update

            def update(params, grads, state):
                grads = {op: dict(ws) for op, ws in grads.items()}
                for op, w in ZEROED:
                    grads[op][w] = jnp.zeros_like(grads[op][w])
                return real_update(params, grads, state)

            opt.update = update
        batch = ff.config.batch_size
        SingleDataLoader(ff, tokens, x)
        SingleDataLoader(ff, ff.label_tensor, y)
        h.cut = cut
        ref = check.reference(h, ff, x[:batch], y[:batch])
        if name in ("sound", "f32_compute"):
            out["router_flip_share"] = check.router_flip_shares(
                h, ff, x[:batch], ref["experts"])
            h.log(f"router flips per expert layer: "
                  f"{out['router_flip_share']}")
        ff.next_batch_all()
        ff.update()
        loss1 = float(ff._last_loss)
        errs = check.update_errors(h, ff, ref)
        out.update(loss1=loss1, reference_loss=ref["loss"],
                   loss_rel=abs(loss1 - ref["loss"]) / abs(ref["loss"]),
                   adam_step1_rel=errs,
                   setup_s=time.perf_counter() - t0)
        ff.fit(epochs=1, verbose=False)         # compiles fit()'s round
        round_s, losses = [], []
        for _ in range(rounds):
            t_r = time.perf_counter()
            ff.fit(epochs=1, verbose=False)
            jax.block_until_ready(ff.params)
            round_s.append(time.perf_counter() - t_r)
            losses.append(float(ff._last_loss))
        out["correct"] = bool(check.verdict(h, ff, loss1, ref, errs, losses))
        tokens_round = x.shape[0] * x.shape[1]
        out.update(round_s=round_s, losses=losses,
                   losses_finite=all(math.isfinite(v) for v in losses),
                   tokens_per_s=tokens_round / min(round_s),
                   breakdown=ff.last_step_breakdown,
                   memory_peak_bytes=int((jax.devices()[0].memory_stats()
                                          or {}).get("peak_bytes_in_use", 0)))
    except Exception as e:      # a variant the compiler refuses is a reading
        out["refused"] = f"{type(e).__name__}: {str(e)[:600]}"
        h.log(f"{name}: REFUSED {out['refused']}")
    finally:
        for f in undo:
            f()
        if ff is not None:
            # the next variant needs the chip's memory: what holds a model
            # alive past this frame (loaders, the recorder) is not waited for
            for a in jax.tree.leaves((ff.params, ff.opt_state)):
                a.delete()
            for dl in ff._dataloaders:
                dl.unstage()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from flexflow_tpu import _env

    if args.rehearsal:
        _env.force_cpu_devices(1)
    elif jax.devices()[0].platform != "tpu":
        print("kanana_controls: not a TPU: nothing is measured",
              file=sys.stderr)
        return 2
    else:
        bench_run.place_compile_cache()
    h = bench_run.load_cell(spec.load_benchmark(ROOT), CELL, seed=args.seed,
                            rehearsal=args.rehearsal)
    generator = spec.load_module("generators", h.traffic["kind"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    batch = h.cut["ffconfig"]["batch_size"]
    seq = h.cut["graph_seq_len"] // h.scale
    x, y = generator.generate(h.traffic, args.seed, batch, seq,
                              z["vocab_size"])
    cut0, results = h.cut, []
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for name in args.only.split(","):
        assert name in CONTROLS, name
        h.log(f"==== control {name}")
        h.cut = cut0
        results.append(run_one(name, h, x, y, args.rounds))
        h.log(f"{name}: { {k: v for k, v in results[-1].items() if k != 'breakdown'} }")
        with open(OUT, "w") as f:
            json.dump({"seed": args.seed, "device":
                       jax.devices()[0].device_kind, "results": results}, f,
                      indent=1)
        gc.collect()
    return bench_run.REHEARSAL_EXIT if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
