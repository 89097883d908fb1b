"""Programs lowered inside the measured window: jax's own lowering events
(every jit cache miss) and, in a serving cell, the engine's recompile
counter, whichever is larger. Must be 0, else `correct` is false: a compile
in the window is set-up that leaked into the measurement."""
NAME, UNIT = "compiles_in_window", "count"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_counter"


def read(ctx):
    return ctx.get("compiles_in_window")
