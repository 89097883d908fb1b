"""The plain reference: a pre-norm decoder's forward pass and cross-entropy in
float32 `jax.numpy`, from the published description of the architecture
(InternLM2 and Mistral share it; so does what `llama_lm` builds):

    h = embed[tokens]
    per layer:  a = RMSNorm(h);  q, k, v = a Wq, a Wk, a Wv  (GQA: fewer k/v
                heads, each repeated over its group);  rotary embedding on q
                and k as the sources apply it (rotate_half, inv_freq =
                theta^(-2i/d));  causal softmax(q k^T / sqrt(d)) v;  h += . Wo
                m = RMSNorm(h);  h += (silu(m Wgate) * (m Wup)) Wdown
    logits = RMSNorm(h) Whead

No kernel, no cache, no batching tricks; matmuls under
`jax.default_matmul_precision("highest")` (on a TPU a float32 matmul
otherwise runs in bf16 passes). It takes the PROGRAM's weights by name and
casts them to float32, one layer at a time, so a 1.9 B-parameter model is
checked beside a full KV pool without holding a float32 copy of it.

Departure from both sources, stated in the configuration files: RMSNorm's
epsilon is 1e-6, which is what `llama_lm` builds (published: 1e-5); the
reference checks the program against what the program claims to compute.

Queries are processed in blocks of QUERY_BLOCK rows so that the score matrix
of a 4096-token sequence stays small; the result does not depend on it.
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, theta):
    """x (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def layer(h, ln1, wq, wk, wv, wo, ln2, w_gate, w_up, w_down, *, theta, eps):
    """One decoder layer on h (S, D); weights in the program's layout:
    wq (D, H, d), wk/wv (D, KVH, d), wo (H, d, D), MLP kernels (in, out)."""
    with jax.default_matmul_precision("highest"):
        ln1, wq, wk, wv, wo, ln2, w_gate, w_up, w_down = map(
            _f32, (ln1, wq, wk, wv, wo, ln2, w_gate, w_up, w_down))
        s = h.shape[0]
        heads, kv_heads, d = wq.shape[1], wk.shape[1], wq.shape[2]
        a = rms_norm(h, ln1, eps)
        q = rotary(jnp.einsum("sd,dhk->shk", a, wq), theta)
        k = rotary(jnp.einsum("sd,dhk->shk", a, wk), theta)
        v = jnp.einsum("sd,dhk->shk", a, wv)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        blocks = []
        for q0 in range(0, s, QUERY_BLOCK):
            q1 = min(s, q0 + QUERY_BLOCK)
            scores = jnp.einsum("qhk,shk->hqs", q[q0:q1], k[:q1]) \
                / math.sqrt(d)
            rows = jnp.arange(q0, q1)[:, None]
            cols = jnp.arange(q1)[None, :]
            scores = jnp.where(cols <= rows, scores, -jnp.inf)
            blocks.append(jnp.einsum("hqs,shk->qhk",
                                     jax.nn.softmax(scores, axis=-1), v[:q1]))
        ctx = jnp.concatenate(blocks, axis=0)
        h = h + jnp.einsum("qhk,hkd->qd", ctx, wo)
        m = rms_norm(h, ln2, eps)
        gate = m @ w_gate
        return h + ((gate * jax.nn.sigmoid(gate)) * (m @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


@jax.jit
def token_losses(logits, labels):
    """Cross-entropy of each position, float32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def forward(params, tokens, *, layers, theta, eps, remat=False):
    """Logits (S, V) of one sequence `tokens` (S,) under the program's
    weights `params` ({op name: {weight name: array}}, llama_lm's names).
    `remat` keeps only each layer's input for a backward pass."""
    one = functools.partial(layer, theta=float(theta), eps=float(eps))
    if remat:
        one = jax.checkpoint(one)
    h = _f32(params["tok_embed"]["kernel"][jnp.asarray(tokens)])
    for i in range(layers):
        at = params[f"attn_{i}"]
        h = one(h, params[f"ln1_{i}"]["scale"], at["wq"], at["wk"],
                at["wv"], at["wo"], params[f"ln2_{i}"]["scale"],
                params[f"ffn_gate_{i}"]["kernel"],
                params[f"ffn_up_{i}"]["kernel"],
                params[f"ffn_down_{i}"]["kernel"])
    return head(h, params["ln_f"]["scale"], params["lm_head"]["kernel"],
                eps=float(eps))


def mean_loss(params, x, y, *, layers, theta, eps):
    """Mean next-token cross-entropy over a batch x (B, S), y (B, S), one
    sequence at a time."""
    total, count = 0.0, 0
    for tokens, labels in zip(x, y):
        logits = forward(params, tokens, layers=layers, theta=theta, eps=eps)
        losses = token_losses(logits, jnp.asarray(labels))
        total += float(jnp.sum(losses))
        count += int(losses.size)
    return total / count


@functools.partial(jax.jit, static_argnames=("layers", "theta", "eps"))
def sequence_loss_and_grads(subset, params, tokens, labels, *, layers, theta,
                            eps):
    """(summed cross-entropy of one sequence, its gradient with respect to
    the float32 weights in `subset` = {op: {weight: array}}); every other
    weight is taken from `params`. Plain reverse mode through `forward`."""
    def loss(sub):
        merged = {op: {**ws, **sub.get(op, {})} for op, ws in params.items()}
        logits = forward(merged, tokens, layers=layers, theta=theta, eps=eps,
                         remat=True)
        return jnp.sum(token_losses(logits, labels))

    return jax.value_and_grad(loss)(subset)


def mean_loss_and_grads(params, x, y, wrt, *, layers, theta, eps):
    """Mean next-token cross-entropy over a batch x (B, S), y (B, S) and its
    gradient with respect to the weights named in `wrt` ([(op, weight)]),
    one sequence at a time."""
    subset = {}
    for op, w in wrt:
        subset.setdefault(op, {})[w] = _f32(params[op][w])
    total, grads, count = 0.0, None, 0
    for tokens, labels in zip(x, y):
        loss, g = sequence_loss_and_grads(
            subset, params, jnp.asarray(tokens), jnp.asarray(labels),
            layers=layers, theta=float(theta), eps=float(eps))
        total += float(loss)
        count += int(labels.size)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / count, jax.tree.map(lambda a: a / count, grads)
