"""The sampler's gate (ops/sampling.py): one `lax.cond` on
`any(temps > 0)` around the warp and the draw.

  (a) what it returns: `sample_tokens` and `sampling_probs` against the
      UNGATED bodies kept here as the oracle, bit for bit, for all-greedy,
      all-sampled and mixed dispatches;
  (b) what it compiles to: one `conditional` under the `sampler` scope (in
      the decode program, inside the scan's body) and no `sort` outside
      that conditional's sampled branch;
  (c) how often it engages: `stats()["sampler_gated_steps"]`.

On the CPU at tiny sizes: the compiled text's fusions differ from the
chip's, the control flow and the scopes it carries do not.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.ops import sampling as S
from hlo_text import branches_of, computations, opcode, reach

# ---- (a) the ungated oracle ------------------------------------------------


def ungated_tokens(logits, temps, top_ps, top_ks, seeds, counters,
                   tag=S.TAG_TARGET):
    """`sample_tokens` as it was before the gate: every row warped and
    drawn, temperature-0 rows resolved by the final `where`."""
    logits = logits.astype(jnp.float32)
    temps = jnp.asarray(temps, jnp.float32)
    masked = S._masked_warped(logits, temps, top_ps, top_ks)
    keys = S.slot_keys(seeds, counters, tag)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, masked)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


def ungated_probs(logits, temps, top_ps, top_ks):
    """`sampling_probs` as it was before the gate."""
    logits = logits.astype(jnp.float32)
    masked = S._masked_warped(logits, temps, top_ps, top_ks)
    probs = jax.nn.softmax(masked, axis=-1)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1),
                            logits.shape[-1], dtype=jnp.float32)
    return jnp.where((temps > 0.0)[:, None], probs, greedy)


TOP_KS = (0, 1, 5, 50)
TOP_PS = (1.0, 0.9, 0.5, 1e-6)
TEMPS = {"greedy": (0.0,),
         "sampled": (0.7, 1.0, 1.3, 0.5),
         "mixed": (0.7, 0.0, 1.0, 0.0, 1.3)}


def dispatch(batch, kind, vocab):
    """One row per (top_k, top_p) pair, temperatures cycling through the
    batch's own: (logits, temps, top_ps, top_ks, seeds, counters)."""
    rs = np.random.RandomState(vocab + len(kind) + len(batch))
    n = len(TOP_KS) * len(TOP_PS)
    if kind == "cutoff-ties":
        # 6 distinct values: every cutoff, top-k or top-p, falls inside a
        # group of tied entries, and so does the argmax
        x = rs.randint(0, 6, (n, vocab)).astype(np.float32)
    else:
        x = rs.randn(n, vocab).astype(np.float32)
    tks, tps = np.meshgrid(TOP_KS, TOP_PS, indexing="ij")
    return (x, np.resize(np.asarray(TEMPS[batch], np.float32), n),
            tps.ravel().astype(np.float32), tks.ravel().astype(np.int32),
            np.arange(n, dtype=np.int32) + 11,
            np.arange(n, dtype=np.int32) * 3)


DISPATCHES = [(batch, kind, vocab) for batch in TEMPS
              for kind in ("f32", "cutoff-ties") for vocab in (97, 5000)]


@pytest.mark.parametrize("batch,kind,vocab", DISPATCHES)
def test_gated_tokens_are_the_ungated_ones_bit_for_bit(batch, kind, vocab):
    args = dispatch(batch, kind, vocab)
    for tag in (S.TAG_TARGET, S.TAG_DRAFT):
        got = np.asarray(jax.jit(S.sample_tokens, static_argnames="tag")(
            *args, tag=tag))
        want = np.asarray(jax.jit(ungated_tokens, static_argnames="tag")(
            *args, tag=tag))
        assert got.dtype == np.int32 and got.shape == (len(args[1]),)
        np.testing.assert_array_equal(got, want)
    cold = args[1] == 0
    np.testing.assert_array_equal(got[cold], np.argmax(args[0], -1)[cold])
    if batch == "sampled":          # the draws are used, not the argmax
        assert (got != np.argmax(args[0], -1)).any()


@pytest.mark.parametrize("batch,kind,vocab", DISPATCHES)
def test_gated_probs_are_the_ungated_ones_bit_for_bit(batch, kind, vocab):
    args = dispatch(batch, kind, vocab)[:4]
    got = np.asarray(jax.jit(S.sampling_probs)(*args))
    want = np.asarray(jax.jit(ungated_probs)(*args))
    assert got.dtype == np.float32 and got.shape == args[0].shape
    np.testing.assert_array_equal(got, want)
    cold = args[1] == 0
    assert ((got[cold] == 1.0).sum(-1) == 1).all()
    assert ((got[cold] > 0).sum(-1) == 1).all()


# ---- (b) the structure -----------------------------------------------------

def gate_of(text):
    """The program's one conditional: (the computation that holds it, its
    op_name, the computations of each branch with all they call). Asserts
    that there is exactly one and that no sort lies outside ONE branch."""
    rows, calls = computations(text)
    conds = [(comp, path, body) for comp, ins in rows.items()
             for _, path, body in ins if opcode(body) == "conditional"]
    assert len(conds) == 1, [c[1] for c in conds]
    comp, path, body = conds[0]
    names = branches_of(body)
    assert len(names) == 2, body
    branches = [reach(calls, [n]) for n in names]
    sorts = [c for c, ins in rows.items()
             for _, _, b in ins if opcode(b) == "sort"]
    with_sort = [br for br in branches if any(c in br for c in sorts)]
    assert sorts and len(with_sort) == 1, (sorts, names)
    assert all(c in with_sort[0] for c in sorts), (sorts, names)
    return comp, path, branches, rows, calls


def test_the_sampler_compiles_to_one_conditional_that_holds_the_sort():
    b, v = 16, 92544
    row = [jax.ShapeDtypeStruct((b,), d) for d in
           (jnp.float32, jnp.float32, jnp.int32, jnp.int32, jnp.int32)]
    lowered = jax.jit(S.sample_tokens).lower(
        jax.ShapeDtypeStruct((b, v), jnp.float32), *row)
    _, path, branches, rows, _ = gate_of(lowered.compile().as_text())
    assert path.split("/")[-2:] == ["sampler", "cond"], path
    # the greedy branch is an argmax and nothing vocabulary-wide besides:
    # no sort, no draw (a draw is a loop of threefry rounds on the CPU)
    greedy = next(br for br in branches
                  if not any(opcode(b) == "sort"
                             for c in br for _, _, b in rows[c]))
    ops = {opcode(b) for c in greedy for _, _, b in rows[c]}
    assert not ops & {"sort", "while", "rng-bit-generator", "exponential"}
    # the lowered program agrees: one sort, one case
    assert len(re.findall(r"stablehlo\.(?:case|if)\b",
                          lowered.as_text())) == 1
    assert len(re.findall(r"stablehlo\.sort\b", lowered.as_text())) == 1


VOCAB = 89


@pytest.fixture(scope="module")
def model():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=64, layers=2, heads=4,
                         kv_heads=2, vocab_size=VOCAB)
    ff.compile(final_tensor=logits)
    return ff


def engine(model, **kw):
    return model.make_serving_engine(serve_slots=4, kv_page_size=4,
                                     max_seq_len=64, **kw)


def prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (n,)).astype(np.int32) for n in lengths]


def test_decode_program_gates_the_sampler_inside_the_scan_body(model):
    eng = engine(model)
    eng.run(prompts(1, (5, 9)), max_new_tokens=6)
    prog = eng._registered[("decode", eng.decode_chunk)]
    assert prog.name == "decode_k8"
    text = prog.text()
    comp, path, _, _, calls = gate_of(text)
    words = path.split("/")
    assert words[-2:] == ["sampler", "cond"], path
    assert "while" in words and "body" in words, path
    # ... and the computation that holds it is the loop's body (or called
    # from it), not the program's entry
    bodies = re.findall(r" while\(.*?body=%?([\w.\-]+)", text)
    assert bodies and comp in reach(calls, bodies)
    # the finite check runs whatever the gate decides: outside the branches
    assert "is-finite" in text or "is_finite" in text


@pytest.mark.parametrize("kind", ("prefill", "verify", "propose"))
def test_every_serve_program_with_a_sampler_holds_the_gate(model, kind):
    """The first token of a prefill, the verify pass's distributions and
    the draft's proposals are gated like the decode scan: a program's
    sorts all lie in a conditional's sampled branch."""
    if kind == "prefill":
        eng = engine(model)
    else:
        eng = engine(model, speculate_k=2, draft_model=model)
    eng.run(prompts(2, (5, 9)), max_new_tokens=6)
    progs = [p for key, p in eng._registered.items() if kind in p.name]
    assert progs, sorted(p.name for p in eng._registered.values())
    for prog in progs:
        rows, calls = computations(prog.text())
        branch = set()
        for ins in rows.values():
            for _, path, body in ins:
                if opcode(body) == "conditional":
                    assert "sampler" in path.split("/"), path
                    branch |= reach(calls, branches_of(body))
        sorts = [c for c, ins in rows.items()
                 for _, _, b in ins if opcode(b) == "sort"]
        assert sorts and all(c in branch for c in sorts), prog.name


# ---- (c) the engine's count ------------------------------------------------

def test_all_greedy_traffic_gates_every_decode_step(model):
    eng = engine(model, decode_chunk=2)
    reqs = eng.run(prompts(3, (5, 9, 3, 7)), max_new_tokens=7)
    st = eng.stats()
    assert st["decode_steps"] > 0
    assert st["sampler_gated_steps"] == st["decode_steps"]
    assert st["sampled_slot_steps"] == 0
    assert all(r.state == "done" for r in reqs)


def test_one_sampled_request_opens_the_gate_and_moves_no_greedy_token(model):
    k = 2
    ps = prompts(3, (5, 9, 3, 7))
    greedy = engine(model, decode_chunk=k)
    want = [r.tokens for r in greedy.run(ps, max_new_tokens=7)]
    eng = engine(model, decode_chunk=k)
    # the sampled request is the shortest: it retires first, and the
    # dispatches after it are gated again
    reqs = [eng.submit(p, 3 if i == 2 else 7,
                       temperature=0.9 if i == 2 else 0.0, top_p=0.9,
                       top_k=5, seed=17)
            for i, p in enumerate(ps)]
    while eng.step():
        pass
    assert [r.state for r in reqs] == ["done"] * 4
    for i, r in enumerate(reqs):
        if i != 2:
            assert r.tokens == want[i]
    st = eng.stats()
    open_steps = -(-(len(reqs[2].tokens) - 1) // k) * k
    assert st["sampled_slot_steps"] == open_steps > 0
    assert st["sampler_gated_steps"] == st["decode_steps"] - open_steps
    assert 0 < st["sampler_gated_steps"] < st["decode_steps"]
