"""`correct` of a training cell whose configuration names its reference
(`"reference": "kanana2"` -> benchmark/reference/kanana2.py, which gives
`mean_loss_and_grads(params, x, y, wrt, sizes, trace)`), outside the timed
window. Checks (a) to (d) are `train_check.py`'s, with that module's loss and
gradient and its Adam step (`adam_first_step`, imported):

(a) the program's step-1 loss against the reference's on batch 0;
(b) the program's FIRST UPDATE of the cut's `update_check_weights` against a
    reference Adam step on the reference's float32 gradient, the |g|-weighted
    error of EACH weight against the limit of its group: the configuration's
    `tolerances["adam_step1_rel"]` maps a prefix of the op's name (`attn`,
    `moe`, ..) to its limit, because the router's top-k is discrete and a
    bf16 residual stream flips near-ties that float32 does not (see below),
    which reaches the expert and router weights and not the attention before
    them. Each weight is held to the limit alone: in a sum over a group a
    small weight with a lost gradient (the router's 0.26 M entries beside an
    expert matrix's 25 M) would weigh nothing;
(c) every window loss finite;
(d) weights and moments held in the type the cut states;
(e) the selection bias (`score_bias`) of every expert layer is bit for bit
    what it was before step 1: it reaches the top-k's indices only, its
    gradient is zero, and Adam's update of a zero gradient is zero.

Counted by `router_flip_shares`, never judged: per expert layer, the share of
(token, expert) choices of the program (its own `ln2_{i}` output through its
own router, in its compute dtype) that are not among the reference's for that
token. It compiles a forward program of its own, so the cell does not run it
(a run's first set-up has to stay well inside the driver's limit);
`benchmark/kanana_controls.py` does, once, beside the planted faults. What
the flips do to the gradients is inside (b)'s per-weight errors, which every
run logs.

The tolerances live in the configuration file with their reasons.
"""

import math
import time

from benchmark import spec
from benchmark.reference.train_check import adam_first_step, state_dtypes


def _module(h):
    return spec.load_module("reference", h.config["reference"])


def router_flip_shares(h, ff, x, chosen):
    """{op name: share of the program's (token, expert) choices on batch `x`
    that the reference (`chosen`: per sequence (expert layers, S, k)) did not
    make for that token}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    layers = range(int(z["first_k_dense_replace"]),
                   int(z["num_hidden_layers"]))
    ln2 = [ff.get_op_by_name(f"ln2_{i}").outputs[0] for i in layers]
    normed = jax.jit(ff.executor.make_forward(ln2))(
        ff.params, ff.bn_state, ff.executor.shard_batch({"input": x}))
    out = {}
    for n, i in enumerate(layers):
        op, m = ff.get_op_by_name(f"moe_{i}"), normed[n]
        # the program's own arithmetic: float32 master weights enter a bf16
        # step rounded to bf16 (runtime/executor.py `apply_graph`)
        p = {k: (v.astype(m.dtype) if v.dtype == jnp.float32 else v)
             for k, v in ff.params[op.name].items()}
        mine = np.asarray(op._route(p, m.reshape(-1, m.shape[-1]))[2])
        theirs = np.concatenate([np.asarray(c[n]) for c in chosen])
        missed = ~(mine[:, :, None] == theirs[:, None, :]).any(-1)
        out[op.name] = float(missed.mean())
    return out


def reference(h, ff, x, y):
    """Before step 1 (the step donates the weights): the reference's loss on
    batch 0, its gradient of the checked weights, a copy of those weights and
    of every selection bias, and the reference's expert choices."""
    import jax.numpy as jnp
    import numpy as np

    wrt = [tuple(w) for w in h.cut["update_check_weights"]]
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    t0 = time.perf_counter()
    trace = {}
    loss, grads = _module(h).mean_loss_and_grads(
        ff.params, x, y[..., 0], wrt, z, trace=trace)
    before = {op: {w: jnp.copy(ff.params[op][w]) for w in ws}
              for op, ws in grads.items()}
    h.log(f"reference on batch 0 ({x.shape[0]} x {x.shape[1]} tokens): loss "
          f"{loss:.6f}, gradient of {len(wrt)} weights, in "
          f"{time.perf_counter() - t0:.1f} s")
    bias = {op: np.asarray(ws["score_bias"]) for op, ws in ff.params.items()
            if "score_bias" in ws}
    return {"loss": loss, "grads": grads, "before": before,
            "experts": trace["experts"], "score_bias": bias}


def update_errors(h, ff, ref):
    """After step 1: {group: {"op.weight": sum |g| |dw_program -
    dw_reference| / sum |g| |dw_reference|}} of each checked weight, by the
    group whose limit it is held to (the longest prefix of the op's name
    among the tolerance's keys)."""
    import jax
    import jax.numpy as jnp

    opt = {k: v for k, v in h.cut["optimizer"].items() if k != "type"}
    groups = sorted(h.config["tolerances"]["adam_step1_rel"], key=len,
                    reverse=True)

    @jax.jit
    def sums(grads, before, after):
        def one(g, w0, w1):
            want = adam_first_step(g, **opt)
            got = w1.astype(jnp.float32) - w0.astype(jnp.float32)
            return (jnp.sum(jnp.abs(g) * jnp.abs(got - want)),
                    jnp.sum(jnp.abs(g) * jnp.abs(want)),
                    jnp.mean((jnp.sign(got) == jnp.sign(want)).astype(
                        jnp.float32)), jnp.mean(jnp.abs(g)))

        return jax.tree.map(one, grads, before, after)

    after = {op: {w: ff.params[op][w] for w in ws}
             for op, ws in ref["grads"].items()}
    read = jax.device_get(sums(ref["grads"], ref["before"], after))
    errs = {}
    for op, ws in ref["grads"].items():
        group = next(g for g in groups if op.startswith(g))
        for w, g in ws.items():
            n, d, agree, mean_g = map(float, read[op][w])
            h.log(f"check (b) {op}.{w} {tuple(g.shape)}: weighted error "
                  f"{n / d:.3e}, sign agrees in {agree:.4f} of the entries, "
                  f"mean |g| {mean_g:.3e}")
            errs.setdefault(group, {})[f"{op}.{w}"] = n / d
    ref["grads"] = ref["before"] = None     # free the copies
    return errs


def verdict(h, ff, loss1, ref, update_errs, losses):
    import jax.numpy as jnp
    import numpy as np

    tol = h.config["tolerances"]
    rel = abs(loss1 - ref["loss"]) / abs(ref["loss"])
    finite = all(math.isfinite(v) for v in losses) and math.isfinite(loss1)
    h.log(f"check (a) step-1 loss {loss1:.6f} vs reference "
          f"{ref['loss']:.6f}: relative difference {rel:.2e} (tolerance "
          f"{tol['step1_loss_rel']}); (c) {len(losses)} window losses "
          f"finite: {finite}")
    ok = rel <= tol["step1_loss_rel"] and finite
    for group, errs in sorted(update_errs.items()):
        limit = tol["adam_step1_rel"][group]
        worst = max(errs, key=lambda w: errs[w] if errs[w] == errs[w]
                    else math.inf)
        h.log(f"check (b) first Adam update of the `{group}` weights vs the "
              f"reference step: largest weighted error {errs[worst]:.3e} "
              f"({worst}; tolerance {limit} for each)")
        ok = ok and all(e <= limit for e in errs.values())
    held, stated = state_dtypes(ff), str(jnp.dtype(ff.config.master_dtype))
    h.log(f"check (d) weights and optimizer state are held in "
          f"{sorted(held)}, the cut states {stated}")
    same = {op: bool(np.array_equal(b, np.asarray(
        ff.params[op]["score_bias"]))) for op, b in ref["score_bias"].items()}
    h.log(f"check (e) selection bias bit for bit what it was: {same}")
    return ok and held == {stated} and all(same.values())
