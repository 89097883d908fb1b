"""The plain reference of the Brumby family (Manifest AI, `model_type`
`brumby`; power retention, arXiv:2507.04239): the forward pass in float32
`jax.numpy`, the QUADRATIC form of the mixer: no state, no chunks, no kernel.

    h = E[tokens]
    per layer i:
        a = RMSNorm(h; w1_i)
        q = RoPE(RMSNorm_head(a W_q))  (H x hd)    k = RoPE(RMSNorm_head(a W_k))  (KV x hd)
        v = a W_v  (KV x hd)           l = logsigmoid(a W_g + b_g)  (KV, the log-decay)
        G_t = sum_{u <= t} l_u
        w_tj = (q_t,i . k_j,g / hd)^2 exp(G_t,g - G_j,g),  j <= t,  g = i // (H / KV)
        y_t,i = sum_j w_tj v_j,g / (sum_j w_tj + eps_n)
        h = h + concat_i(y) W_o
        [g | u] = RMSNorm(h; w2_i) W_in;  h = h + (silu(g) * u) W_out
    logits = RMSNorm(h; w_f) W_head                       # untied head

Matmuls run under `jax.default_matmul_precision("highest")`. It takes the
PROGRAM's weights by name (`brumby_lm`'s: `tok_embed`, `norm1_{i}`,
`retention_{i}`, `norm2_{i}`, `mlp_{i}`, `norm_f`, `lm_head`) and casts them to
float32 one layer at a time; nothing is imported from the program.

What a cache holds after `rows` tokens is, by the same equations, the ONE
weighted sum over those tokens

    S = sum_{j < rows} exp(G_{rows-1} - G_j) phi(k_j) v_j^T,   z = the same with v = 1,
    phi(x)[d, a] = c_d x_a x_{(a - d) mod hd} / hd,  d = 0 .. hd / 2,  c_0 = c_{hd/2} = 1, else sqrt 2

(phi(x) . phi(y) = (x . y / hd)^2: the symmetric square held by diagonals, the
order in which the program holds its state, so the two compare entry by
entry), handed out as `states[op] = {"s": (KV, hd / 2 + 1, hd [value], hd
[a]), "z": (KV, hd / 2 + 1, hd)}`. It is one sum, not a recurrence.

Departures from the published description, each the same function: queries
are processed in blocks of QUERY_BLOCK rows under a dense causal mask over ALL
keys (one compiled shape whatever the block, so that 32 k tokens fit and
compile once; the result does not depend on the block); the state's sum runs
one KV head at a time and the MLP MLP_BLOCK rows at a time; `logit_rows` = (first, last) computes the head for
those rows alone (32 k x 151936 float32 logits would be 19 GB); the source
holds keys and values below a switch-over length and the state above it,
where this file holds neither. What `config.json` does not carry (power 2,
the gate, eps_n, the per-head norms and the rotary) is listed under `assumed`
in the configuration file.
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
MLP_BLOCK = 1024


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """Rotate-half rotary on x (S, heads, hd) at positions 0 .. S - 1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def phi(x):
    """(.., hd) -> (.., hd / 2 + 1, hd): the symmetric square by diagonals."""
    hd = x.shape[-1]
    c = jnp.asarray([1.0] + [math.sqrt(2.0)] * (hd // 2 - 1) + [1.0],
                    jnp.float32) / hd
    rolled = jnp.stack([jnp.roll(x, d, axis=-1)
                        for d in range(hd // 2 + 1)], axis=-2)
    return rolled * x[..., None, :] * c[:, None]


@functools.partial(jax.jit, static_argnames=("theta", "eps", "eps_n",
                                             "with_state"))
def retention(h, norm, wq, wk, wv, wg, gate_bias, q_norm, k_norm, wo, rows,
              *, theta, eps, eps_n, with_state):
    """h + retention(RMSNorm(h)) on h (S, D), the quadratic form; weights in
    the program's layout: wq (D, H, hd), wk / wv (D, KV, hd), wg (D, KV), wo
    (H, hd, D). S is a multiple of QUERY_BLOCK (the callers pad). Beside it,
    `with_state`, what a cache holds after the first `rows` tokens."""
    with jax.default_matmul_precision("highest"):
        (norm, wq, wk, wv, wg, gate_bias, q_norm, k_norm, wo) = map(
            _f32, (norm, wq, wk, wv, wg, gate_bias, q_norm, k_norm, wo))
        s = h.shape[0]
        heads, kv, hd = wq.shape[1], wk.shape[1], wq.shape[2]
        a = rms_norm(h, norm, eps)
        q = rope(rms_norm(jnp.einsum("sd,dhk->shk", a, wq), q_norm, eps),
                 theta).reshape(s, kv, heads // kv, hd)
        k = rope(rms_norm(jnp.einsum("sd,dhk->shk", a, wk), k_norm, eps),
                 theta)
        v = jnp.einsum("sd,dhk->shk", a, wv)
        g = jnp.cumsum(jax.nn.log_sigmoid(a @ wg + gate_bias), axis=0)
        cols = jnp.arange(s)[None, :]

        def block(args):
            qb, gb, q0 = args
            sc = jnp.einsum("tgrk,jgk->grtj", qb, k) / hd
            at = q0 + jnp.arange(QUERY_BLOCK)[:, None]
            decay = jnp.exp(jnp.where(
                cols <= at, gb.T[:, :, None] - g.T[:, None, :], -jnp.inf))
            w = sc * sc * decay[:, None]                  # (KV, R, T, S)
            return (jnp.einsum("grtj,jgv->tgrv", w, v)
                    / (w.sum(-1).transpose(2, 0, 1) + eps_n)[..., None])

        nb = s // QUERY_BLOCK
        y = jax.lax.map(block, (
            q.reshape(nb, QUERY_BLOCK, kv, heads // kv, hd),
            g.reshape(nb, QUERY_BLOCK, kv),
            jnp.arange(nb) * QUERY_BLOCK)).reshape(s, heads, hd)
        out = h + jnp.einsum("shk,hkd->sd", y, wo)
        if not with_state:
            return out, None, None
        # the weights of the one sum: 0 from row `rows` on
        keep = jnp.where(jnp.arange(s)[:, None] < rows,
                         jnp.exp(g[rows - 1] - g), 0.0)   # (S, KV)

        def one_head(args):
            kg, vg, wgt = args                            # (S, hd), (S, hd), (S,)
            pk = phi(kg) * wgt[:, None, None]             # (S, ND, hd)
            return jnp.einsum("jda,jv->dva", pk, vg), pk.sum(axis=0)

        st, z = jax.lax.map(one_head, (k.transpose(1, 0, 2),
                                       v.transpose(1, 0, 2), keep.T))
        return out, st, z


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp(h, norm, w_in, w_out, *, eps):
    """h + MLP(RMSNorm(h)), MLP_BLOCK rows at a time where they divide S (32 k
    rows of 2 x 17408 float32 at once would be 4.4 GB)."""
    with jax.default_matmul_precision("highest"):
        norm, w_in, w_out = map(_f32, (norm, w_in, w_out))
        f = w_in.shape[1] // 2

        def rows(hb):
            gu = rms_norm(hb, norm, eps) @ w_in
            return hb + (silu(gu[:, :f]) * gu[:, f:]) @ w_out

        s = h.shape[0]
        if s % MLP_BLOCK or s == MLP_BLOCK:
            return rows(h)
        return jax.lax.map(rows, h.reshape(s // MLP_BLOCK, MLP_BLOCK,
                                           -1)).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, norm_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(norm_f), eps) @ _f32(w_head)


def forward(params, tokens, sizes, states=None, rows=None, logit_rows=None,
            state_layers=None):
    """Logits of one sequence `tokens` (S,) under the program's weights
    `params` ({op name: {weight name: array}}, brumby_lm's names): all (S, V)
    of them, or rows `logit_rows` = (first, last) alone. `sizes` holds the
    configuration's keys (`num_hidden_layers`, `rope_theta`, `rms_norm_eps`,
    `retention_norm_eps`). `states`, if a dict, receives {"s", "z"} of each
    retention layer (of `state_layers` alone, when given) after the first
    `rows` tokens (all of them by default) under the layer's op name: what a
    cache holds when the sequence stops there. The rows behind are computed
    and change nothing (causal), so a caller can pad to a length it has
    compiled; the length is rounded up to QUERY_BLOCK here."""
    eps = float(sizes["rms_norm_eps"])
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    rows = jnp.int32(n if rows is None else rows)
    tokens = jnp.pad(tokens, (0, -n % QUERY_BLOCK))
    h = _f32(params["tok_embed"]["kernel"][tokens])
    for i in range(int(sizes["num_hidden_layers"])):
        m = params[f"retention_{i}"]
        wanted = states is not None and (state_layers is None
                                         or i in state_layers)
        h, st, z = retention(
            h, params[f"norm1_{i}"]["scale"], m["wq"], m["wk"], m["wv"],
            m["wg"], m["gate_bias"], m["q_norm"], m["k_norm"], m["wo"], rows,
            theta=float(sizes["rope_theta"]), eps=eps,
            eps_n=float(sizes["retention_norm_eps"]), with_state=wanted)
        if wanted:
            states[f"retention_{i}"] = {"s": st, "z": z}
        f = params[f"mlp_{i}"]
        h = mlp(h, params[f"norm2_{i}"]["scale"], f["w_in"], f["w_out"],
                eps=eps)
    lo, hi = (0, n) if logit_rows is None else logit_rows
    return head(h[lo:hi], params["norm_f"]["scale"],
                params["lm_head"]["kernel"], eps=eps)
