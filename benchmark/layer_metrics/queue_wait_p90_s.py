"""90th percentile, over the window's finished requests, of the time a request
sat in the engine's queue: `Request.t_admit - Request.t_submit`, both stamps
of the program's own clock (`t_admit` is the read `_admit` makes when the
request leaves the queue for a slot; the ring's `queue_wait` span is the same
interval). With it a request's time to first token from when it was due
splits into generator lateness (harness) + queue wait + prefill. A program
whose requests carry no `t_admit` reports nothing."""
NAME, UNIT = "queue_wait_p90_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "serve_tokens_per_s", "program_span"


def read(ctx):
    from benchmark import stats

    waits = []
    for rec in ctx.get("records") or ():
        req = rec.get("request")
        if rec["state"] != "done" or not getattr(req, "t_admit", 0.0):
            continue
        waits.append(req.t_admit - req.t_submit)
    return stats.percentile(waits, 90) if waits else None
