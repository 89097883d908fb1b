"""A held expert share's grouped lowering works on `held_rows_cap` sorted rows
a pass whether or not the call has a gradient (ops/moe.py `_held_passes`,
PR 41): forward-only calls against a reference that passes through no
`_held_passes` (the layer that holds every expert, the other experts
silenced), what the lowered program holds, and the `expert_rows` counter from
the op up to `ServingEngine.stats()`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.exaone_moe import exaone_moe_lm
from flexflow_tpu.ops import moe as moe_mod
from flexflow_tpu.ops.moe import MoE
from flexflow_tpu.runtime import telemetry

N, D, F, EXPERTS, K, HELD = 96, 32, 16, 32, 4, (8, 4)
FORMS = {"swiglu": dict(expert="swiglu"),
         "relu2-latent": dict(expert="relu2", latent_dim=16)}


def moe_op(held=None, n=N, **form):
    ff = FFModel(FFConfig(batch_size=n, mesh_shape={"data": 1}))
    x = ff.create_tensor([n, D], name="x")
    return MoE(ff, "moe", [x], EXPERTS, F, K, None, scoring="sigmoid",
               score_bias=0.1, routed_scaling=2.448, shared_hidden_dim=2 * F,
               experts_held=held, **{"expert": "swiglu", **form})


def weights(op, seed=0):
    rs = np.random.RandomState(seed)
    return {w.name: jnp.asarray(rs.randn(*w.shape) * (
        0.1 if w.name == "score_bias" else w.shape[-2] ** -0.5), jnp.float32)
        for w in op.weight_specs()}


def share(p, first, count):
    return {n: (v[first:first + count] if n in MoE._EXPERT_WEIGHTS else v)
            for n, v in p.items()}


def only(p, first, count):
    """Every expert outside first .. first + count - 1 silenced."""
    e = jnp.arange(p["w_down"].shape[0])
    keep = (e >= first) & (e < first + count)
    return {**p, "w_down": p["w_down"] * keep[:, None, None]}


@pytest.mark.parametrize("slack", [2.0, 0.25], ids=["one-pass", "passes"])
@pytest.mark.parametrize("rows", ["all-live", "padded"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_forward_only_held_share_is_the_silenced_uncut_layer(
        monkeypatch, form, rows, slack):
    """No gradient anywhere: the share's compact passes (one, or several at
    slack 0.25) give what the uncut layer's all-rows form gives with the
    other experts silenced; a bucket's padding rows (dead by `row_mask`)
    get the shared expert alone and count nowhere."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    whole, part = moe_op(**FORMS[form]), moe_op(HELD, **FORMS[form])
    p = weights(whole)
    x = jax.random.normal(jax.random.PRNGKey(7), (N, D))
    mask = None if rows == "all-live" else jnp.arange(N) < 70
    want = whole.forward(only(p, *HELD), [x], row_mask=mask)[0]
    routing, given = [], []
    got = part.forward(share(p, *HELD), [x], row_mask=mask, routing=routing,
                       expert_rows=given)[0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    cap = moe_mod.held_rows_cap(N, K, HELD[1], EXPERTS)
    held = int(routing[0][0])
    assert cap < N * K and held > 0
    assert (held <= cap) == (slack == 2.0)
    assert int(given[0]) == cap * -(-held // cap)
    if mask is not None:
        shared = part._shared_expert(share(p, *HELD), x)
        np.testing.assert_array_equal(got[70:], shared[70:])
        assert np.abs(np.asarray(got[:70] - shared[:70])).max() > 1e-3


@pytest.mark.parametrize("form", sorted(FORMS))
def test_no_assignment_held_here_is_no_pass(monkeypatch, form):
    """Every assignment held elsewhere (the selection bias keeps the held
    experts out of every top-k): zero passes, the shared expert alone."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    part = moe_op(HELD, **FORMS[form])
    p = share(weights(moe_op(**FORMS[form])), *HELD)
    first, count = HELD
    p["score_bias"] = p["score_bias"].at[first:first + count].set(-1e9)
    x = jax.random.normal(jax.random.PRNGKey(8), (N, D))
    routing, given = [], []
    got = part.forward(p, [x], routing=routing, expert_rows=given)[0]
    assert int(routing[0][0]) == 0 and int(given[0]) == 0
    np.testing.assert_array_equal(got, part._shared_expert(p, x))


def _avals(jaxpr):
    """Every value's aval in a jaxpr and in the jaxprs its equations hold
    (a `while`'s body, a `custom_vjp`'s call, a `pjit`)."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("held,has_all_rows", [(HELD, False), (None, True)],
                         ids=["held-share", "every-expert"])
def test_a_forward_only_held_program_holds_no_array_of_all_rows(
        held, has_all_rows):
    """The traced program of a forward-only call of more than
    MOE_STREAM_MAX_ROWS rows: a held share's has no array of N*k rows (the
    sorted ORDER is N*k integers, one to a row where it indexes), a layer
    that holds every expert keeps its (N*k, D) gather."""
    n = 256
    op = moe_op(held, n=n)
    p = weights(op)
    closed = jax.make_jaxpr(lambda q, x: op.forward(q, [x])[0])(
        p, jnp.zeros((n, D)))
    wide = [a.shape for a in _avals(closed.jaxpr)
            if len(getattr(a, "shape", ())) >= 2 and a.shape[0] == n * K
            and np.prod(a.shape[1:]) > 1]
    assert bool(wide) == has_all_rows, wide
    if has_all_rows:
        assert (n * K, D) in wide
    else:
        cap = moe_mod.held_rows_cap(n, K, HELD[1], EXPERTS)
        assert cap == 256 < n * K
        assert any(len(getattr(a, "shape", ())) == 2 and a.shape[0] == cap
                   for a in _avals(closed.jaxpr))


# ---- the counter, up to the engine's spans and stats ------------------------

LAYER_TYPES = ["sliding_attention", "full_attention"]
SERVE_K = 3


def served(held):
    cfg = FFConfig(batch_size=1, mesh_shape={"data": 1}, seed=3)
    ff = FFModel(cfg)
    _, logits = exaone_moe_lm(
        ff, 1, seq_len=64, hidden=64, layers=2, heads=4, kv_heads=2,
        head_dim=16, layer_types=LAYER_TYPES, sliding_windows=[8, 0],
        mlp_layer_types=["sparse", "sparse"], ffn_hidden=96, num_experts=8,
        experts_per_token=SERVE_K, expert_hidden=48, experts_held=held,
        score_bias_std=0.05, vocab_size=128)
    ff.compile(final_tensor=logits)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64, prefix_cache=False,
                                 decode_chunk=2)
    since = len(telemetry.tracer().events())
    lengths = (23, 30, 9)
    reqs = [eng.submit(np.random.RandomState(n).randint(1, 128, (n,))
                       .astype(np.int32), max_new_tokens=2) for n in lengths]
    while eng.pending():
        eng.step()
    assert all(r.state == "done" for r in reqs)
    spans = [e["args"] for e in telemetry.tracer().events()[since:]
             if e["name"] == "prefill" and e["pid"] == eng._tm_track]
    assert len(spans) == len(lengths)
    return eng, spans


@pytest.mark.parametrize("held", [(2, 2), None], ids=["held-share", "every"])
def test_prefill_spans_carry_expert_rows_and_stats_sum_them(monkeypatch,
                                                            held):
    """`expert_rows` on each `ff.prefill` span beside `assignments`:
    counted inside the program for a held share (passes x rows a pass, a
    third entry of its routing output), the program's static N*k where
    every expert is held (whose programs keep their (2,) output);
    `stats()` sums both over the grouped prefills."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    eng, spans = served(held)
    for a in spans:
        # two expert layers: the first over the bucket's rows, the last
        # over the prompt's one last row
        calls = [a["bucket"], 1]
        if held is None:
            assert a["expert_rows"] == SERVE_K * sum(calls)
            assert a["assignments"] == SERVE_K * (a["prompt_tokens"] + 1)
        else:
            caps = [moe_mod.held_rows_cap(n, SERVE_K, 2, 8) for n in calls]
            assert caps[0] < SERVE_K * a["bucket"]
            # whole passes of the first call's cap, the last row's at most 3
            assert a["expert_rows"] >= a["assignments"] > 0
            assert a["expert_rows"] - caps[0] * (
                a["expert_rows"] // caps[0]) in (0, SERVE_K)
    st = eng.stats()
    assert st["moe_expert_rows"] == sum(a["expert_rows"] for a in spans) > 0
    assert st["moe_prefill_assignments"] == sum(a["assignments"]
                                                for a in spans)
    static = {k: sum(v) for k, v in eng._moe_static_rows.items()}
    assert set(static) == {k for k in eng._moe_took if k[0] == "prefill"}
    # a one-row call's rows are static in both (its cap is all of them)
    assert all(v == SERVE_K * ((k[1] if held is None else 0) + 1)
               for k, v in static.items())
