"""Median time to first token from when the request was due: the wait for
the running decode chunk and the prefills admitted ahead, plus the request's
own prefill. Recorded, not judged: over a window's 82 requests its spread is
3.9-5.4 % (my chip runs, PR 22), more than a bound of at most 10 % admits.
It moves `serve_tokens_per_s`, the one speed metric judged in its cell: a
longer wait or prefill is what takes the cell over its knee."""
NAME, UNIT = "ttft_p50_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "serve_tokens_per_s", "host_clock"


def read(ctx):
    return (ctx.get("window") or {}).get("ttft_p50_s")
