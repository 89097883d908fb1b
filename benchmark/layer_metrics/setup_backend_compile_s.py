"""Seconds jax spent in `backend_compile_duration`, summed over the lifecycle
spans that began before the window (`backend_s`, booked as
`setup_trace_lower_s`'s counts are): XLA and Mosaic compiling a program, or
the persistent cache's load where it hit (jax clocks the load inside the
same interval). Large beside a `setup_cache_miss_programs` of 0, it is the
cache's own load time; with misses, it is what a cache key without source
locations or a program compiled ahead would take away (ROADMAP S14)."""
NAME, UNIT = "setup_backend_compile_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_counter"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    return sr.count(red["spans"], "backend_s") if red else None
