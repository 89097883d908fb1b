"""Programs the persistent compilation cache was asked for and did not hold,
summed over the lifecycle spans that began before the window: jax's
`compile_requests_use_cache` events less its `cache_hits` (`cache_requests` -
`cache_hits`). 0 in a warm run. Above 0 it says that the traced run was
cold, and after an identical run it names an eviction (the machine's cache
is capped) or a cache key that moved. 0 is a reading."""
NAME, UNIT = "setup_cache_miss_programs", "count"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_counter"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    if not red:
        return None
    return sr.count(red["spans"], "cache_requests") \
        - sr.count(red["spans"], "cache_hits")
