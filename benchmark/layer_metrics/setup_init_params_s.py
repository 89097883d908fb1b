"""Seconds of set-up inside the program's `init_params` and `init_optimizer`
spans (`FFModel.compile()`: one jitted program a weight, then the optimizer's
moments; benchmark/setup_reduce.py). It is what one jitted init for the whole
model (ROADMAP S14) would shorten: warm, the weights' programs are traced,
lowered and loaded from the cache one after another. A program that opens no
such span reports nothing."""
NAME, UNIT = "setup_init_params_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_span"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    spans = sr.named(red["spans"], "init_params", "init_optimizer") \
        if red else []
    return sr.seconds(spans) if spans else None
