"""Seconds of set-up inside the OUTERMOST `compile` spans that began before
the window: each is one program's first call until its outputs are ready
(trace, lower, backend compile or the cache's load, and the first run), for
the engine's programs (`serving.py` `_compiled_call`) and a model's
(`FFModel._first_call`: the train step, the scanned step, eval, predict).
What a program compiled ahead, or fewer prompt buckets, would shorten
(ROADMAP S14). A program that opens no such span reports nothing."""
NAME, UNIT = "setup_compile_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_span"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    spans = sr.outermost(sr.named(red["spans"], "compile")) if red else []
    return sr.seconds(spans) if spans else None
