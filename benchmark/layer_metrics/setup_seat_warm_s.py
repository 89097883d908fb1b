"""Seconds inside the engine's blocking `run` / `prefill_into_cache` spans
that ENDED before the window's first request, less the `compile` spans nested
in them: the warm-up and the seating of the resident documents as pure
execution (prefill and decode on the chip, the host's tick between). It is
what seating documents faster (ROADMAP S16 (2)) would shorten, apart from the
compiles that `setup_compile_s` holds. A serving cell's; a program that opens
no such span reports nothing."""
NAME, UNIT = "setup_seat_warm_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_span"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    return sr.seat_warm_s(red) if red else None
