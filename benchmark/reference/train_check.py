"""`correct` of a training cell, outside the timed window.

(a) The program's step-1 loss equals the reference's loss on the same batch
    under the same (initial) weights: the forward pass.
(b) Where the cut names `update_check_weights`: the program's FIRST UPDATE of
    those weights equals a reference Adam step on the reference's float32
    gradient. Only the weights before and after `update()` are read, no
    internals. The error is weighted by |gradient|, so it is the share of the
    reference step's first-order loss decrease the program's update could
    miss: entries whose gradient is lost in rounding weigh nothing, a dropped
    gradient, a weight kept in fewer bits than the update needs, or a wrong
    moment shows in full. This is the check of the backward pass, the master
    weights and the optimizer.
(c) Every loss read in the window is finite.
(d) The weights and the optimizer's moments are held in the type the cut
    states (`master_dtype`). (b) cannot see the moments' precision: they
    first act in step 2, where the gradient noise of bf16 compute (which the
    configuration allows) is larger than the rounding of a bf16 moment.

The tolerances live in the configuration file with their reasons."""

import math
import time


def sizes(h):
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    return dict(layers=z["num_hidden_layers"], theta=float(z["rope_theta"]),
                eps=float(h.config["program_rms_norm_eps"]))


def reference(h, ff, x, y):
    """Before step 1 (the step donates the weights): the reference's loss on
    batch 0 and, for (b), its gradient and a copy of the weights checked."""
    import jax.numpy as jnp

    from benchmark.reference import decoder

    wrt = [tuple(w) for w in h.cut.get("update_check_weights", ())]
    t0 = time.perf_counter()
    if not wrt:
        loss = decoder.mean_loss(ff.params, x, y[..., 0], **sizes(h))
        grads = before = None
    else:
        loss, grads = decoder.mean_loss_and_grads(ff.params, x, y[..., 0],
                                                  wrt, **sizes(h))
        before = {op: {w: jnp.copy(ff.params[op][w]) for w in ws}
                  for op, ws in grads.items()}
    h.log(f"reference on batch 0 ({x.shape[0]} x {x.shape[1]} tokens): loss "
          f"{loss:.6f}, gradient of {len(wrt)} weights, in "
          f"{time.perf_counter() - t0:.1f} s")
    return {"loss": loss, "grads": grads, "before": before}


def adam_first_step(g, alpha, beta1=0.9, beta2=0.999, epsilon=1e-8):
    """The change of a weight in Adam's first step from m = v = 0 (Kingma &
    Ba 2015, section 2's form: step size alpha_t = alpha sqrt(1 - beta2^t) /
    (1 - beta1^t), update -alpha_t m / (sqrt(v) + epsilon)), no decay."""
    import jax.numpy as jnp

    m = (1.0 - beta1) * g
    v = (1.0 - beta2) * g * g
    alpha_t = alpha * math.sqrt(1.0 - beta2) / (1.0 - beta1)
    return -alpha_t * m / (jnp.sqrt(v) + epsilon)


def update_error(h, ff, ref):
    """After step 1: sum |g| |dw_program - dw_reference| / sum |g|
    |dw_reference| over the checked weights, or None without (b)."""
    if not ref["grads"]:
        return None
    import jax
    import jax.numpy as jnp

    opt = {k: v for k, v in h.cut["optimizer"].items() if k != "type"}

    @jax.jit
    def sums(g, w0, w1):
        want = adam_first_step(g, **opt)
        got = w1.astype(jnp.float32) - w0.astype(jnp.float32)
        return (jnp.sum(jnp.abs(g) * jnp.abs(got - want)),
                jnp.sum(jnp.abs(g) * jnp.abs(want)),
                jnp.mean((jnp.sign(got) == jnp.sign(want)).astype(
                    jnp.float32)))

    num = den = 0.0
    for op, ws in ref["grads"].items():
        for w, g in ws.items():
            n, d, agree = map(float, sums(g, ref["before"][op][w],
                                          ff.params[op][w]))
            h.log(f"check (b) {op}.{w} {tuple(g.shape)}: weighted error "
                  f"{n / d:.3e}, sign agrees in {agree:.4f} of the entries, "
                  f"mean |g| {float(jnp.mean(jnp.abs(g))):.3e}")
            num, den = num + n, den + d
    ref["grads"] = ref["before"] = None     # free the copies
    return num / den


def state_dtypes(ff):
    """The set of floating types among the weights and the optimizer state."""
    import jax
    import jax.numpy as jnp

    return {str(a.dtype) for a in jax.tree.leaves((ff.params, ff.opt_state))
            if jnp.issubdtype(a.dtype, jnp.floating)}


def verdict(h, ff, loss1, ref, update_err, losses):
    import jax.numpy as jnp

    tol = h.config["tolerances"]
    rel = abs(loss1 - ref["loss"]) / abs(ref["loss"])
    finite = all(math.isfinite(v) for v in losses) and math.isfinite(loss1)
    h.log(f"check (a) step-1 loss {loss1:.6f} vs reference "
          f"{ref['loss']:.6f}: relative difference {rel:.2e} (tolerance "
          f"{tol['step1_loss_rel']}); (c) {len(losses)} window losses "
          f"finite: {finite}")
    ok = rel <= tol["step1_loss_rel"] and finite
    if update_err is not None:
        h.log(f"check (b) first Adam update vs the reference step: weighted "
              f"error {update_err:.3e} (tolerance {tol['adam_step1_rel']})")
        ok = ok and update_err <= tol["adam_step1_rel"]
    held, stated = state_dtypes(ff), str(jnp.dtype(ff.config.master_dtype))
    h.log(f"check (d) weights and optimizer state are held in "
          f"{sorted(held)}, the cut states {stated}")
    return ok and held == {stated}
